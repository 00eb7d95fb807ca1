"""The operation and byte counts behind ``mfu.*`` and ``generate_roofline.*``,
against hand counts at yi-6b's published widths."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import counts  # noqa: E402
import harness  # noqa: E402

with open(os.path.join(HERE, "configs", "yi-6b.json")) as f:
    YI = json.load(f)

# Per layer: q 4096x4096, k and v 4096x512, o 4096x4096, gate|up
# 4096x22016, down 11008x4096.
LAYER_WEIGHTS = 4096 * (4096 + 2 * 512) + 4096 * 4096 + 3 * 4096 * 11008
HEAD = 4096 * 64000


def test_layer_weights_and_parameter_count():
    assert LAYER_WEIGHTS == 173_015_040
    # With the embedding, 65 RMSNorm weights of 4096: the published
    # 6,061,035,520 parameters.
    assert (32 * LAYER_WEIGHTS + 2 * HEAD + 65 * 4096
            == 6_061_035_520)


def test_request_flops_by_hand():
    # Prompt 4, 3 new tokens: prefill over 4 positions, 2 decode steps,
    # logits at the 3 positions that sample.
    prefill = 2 * 32 * LAYER_WEIGHTS * 4
    prefill_attn = 2 * 2 * 32 * 128 * (4 * 5 // 2) * 32   # causal: 10 pairs
    head = 2 * HEAD * 3
    decode = 2 * 32 * LAYER_WEIGHTS * 2
    decode_attn = 2 * 2 * 32 * 128 * (5 + 6) * 32          # 5 then 6 keys
    assert prefill + prefill_attn + head + decode + decode_attn \
        == 68_021_649_408
    assert counts.request_flops(YI, 4, 3) == 68_021_649_408


def test_flops_of_a_batch_are_its_requests_flops():
    dots = counts.generate_dots(YI, 8, 1024, 256)
    assert sum(d.flops * d.count for d in dots) == pytest.approx(
        8 * counts.request_flops(YI, 1024, 256))


def test_decode_step_bytes_by_hand():
    # One decode step at batch 8 after a 1024-token prompt: every weight
    # read once (bf16), activations in and out, and the K and V caches
    # of 1025 positions (4 KV heads of 128, bf16) per layer.
    dots = [d for d in counts.generate_dots(YI, 8, 1024, 2)
            if d.name.startswith("decode")]
    weights = 2 * (32 * LAYER_WEIGHTS + HEAD)
    acts = 32 * 2 * 8 * (4096 + 4096 + 4096 + 512 + 4096 + 512 + 4096
                         + 4096 + 4096 + 22016 + 11008 + 4096) \
        + 2 * 8 * (4096 + 64000)
    cache = 2 * 32 * (8 * 4 * 1025 * 128 * 2 + 8 * 4096 * 2)
    assert weights + acts + cache == 12_174_139_392
    assert sum(d.bytes * d.count for d in dots) == 12_174_139_392


def test_least_time_names_the_binding_bound():
    peak, bw = 197e12, 819e9
    decode = [d for d in counts.generate_dots(YI, 8, 1024, 2)
              if d.name.startswith("decode")]
    t, compute_share = counts.least_time(decode, peak, bw)
    assert compute_share == 0.0                      # bandwidth-bound
    assert t == pytest.approx(12_174_139_392 / bw)
    prefill = [d for d in counts.generate_dots(YI, 8, 1024, 1)
               if d.name.startswith("prefill") and "head" not in d.name]
    t, compute_share = counts.least_time(prefill, peak, bw)
    assert compute_share == 1.0                      # compute-bound
    assert t == pytest.approx(sum(d.flops * d.count for d in prefill) / peak)


class _Trace:
    def __init__(self, busy_s):
        self.ops = {0: []}
        self._busy_s = busy_s

    def busy_s(self, device):
        return self._busy_s


def _view(trace=None, window_s=1.0):
    cell = harness.Cell("c", 1, YI, {"prompt_len": 4, "new_tokens": 3,
                                     "batch": 2}, {}, [], [])
    reqs = [harness.Request(0.0, [0] * 4, out=[0] * 3) for _ in range(2)]
    served = harness.Served(0.0, window_s, reqs,
                            [2], 0, 0.0)
    return harness.RunView(cell, {"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9}, served, trace)


def test_mfu_reader():
    mfu = harness.reader("mfu.offline")
    assert mfu(_view(window_s=2.0)) == pytest.approx(
        100 * 2 * 68_021_649_408 / (2.0 * 197e12))


def test_generate_roofline_reader():
    roof = harness.reader("generate_roofline.offline")
    least, _ = counts.least_time(counts.generate_dots(YI, 2, 4, 3),
                                 197e12, 819e9)
    assert roof(_view(_Trace(2 * least))) == pytest.approx(50.0)
    assert roof(_view(None)) is None
    assert roof(_view(_Trace(0.0))) is None
