"""Operations and bytes that RWKV-6 serving needs, counted from a config's
shapes.

What the algorithm needs, as ``counts.py`` counts a transformer: each
weight read once per step (the prefill is one step), activations read
and written once, logits only where a token is sampled, no padding or
recomputation.  Per layer and token: the r, k, v, g and output
projections; the low-rank token mixes and decay; the channel mix (key,
value, receptance); and the WKV recurrence in its recurrent form, per
head of size C: the state update ``S = diag(w) S + k^T v`` (3 C^2), the
readout ``r S`` (2 C^2) and the bonus ``(r . (u * k)) v`` (4 C), with
the float32 state read and written once per layer call (the whole
prompt, or one decode step) and r, k, v (bfloat16), the log decay
(float32) and the output (bfloat16) passed once.  ``rwkv_mfu``,
``wkv_roofline`` and ``rwkv_decode_roofline`` read them.
"""

from __future__ import annotations

import dataclasses

from counts import Dot, least_time

N_MIX = 5                    # r, k, v, g, w


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int                   # hidden size
    hs: int                  # head size (C)
    ff: int                  # channel-mix width
    vocab: int
    layers: int
    mix_rank: int
    decay_rank: int

    @property
    def h(self) -> int:
        return self.d // self.hs


def dims(conf: dict) -> Dims:
    return Dims(d=conf["hidden_size"], hs=conf["head_size"],
                ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                layers=conf["num_hidden_layers"],
                mix_rank=conf["time_mix_extra_dim"],
                decay_rank=conf["time_decay_extra_dim"])


def _mm(name, m, k, n, count, groups=1, a_bytes=2):
    """``groups`` (m, k) x (k, n) products against weights read once."""
    return Dot(name, 2.0 * groups * m * k * n,
               float(groups * (k * n * 2 + (m * k + m * n) * a_bytes)),
               count)


def _layer(t: Dims, m: int, count: int, tag: str):
    """The dots of one layer over ``m`` tokens, ``count`` times."""
    d = t.d
    return [_mm(f"{tag}.{p}", m, d, d, count) for p in "rkvgo"] + [
        _mm(f"{tag}.mix_lora", m, d, N_MIX * t.mix_rank, count),
        _mm(f"{tag}.mix_lora", m, t.mix_rank, d, count, groups=N_MIX),
        _mm(f"{tag}.decay_lora", m, d, t.decay_rank, count),
        _mm(f"{tag}.decay_lora", m, t.decay_rank, d, count),
        _mm(f"{tag}.cm_k", m, d, t.ff, count),
        _mm(f"{tag}.cm_v", m, t.ff, d, count),
        _mm(f"{tag}.cm_r", m, d, d, count)]


def _wkv(t: Dims, batch: int, tokens: int, count: int, tag: str):
    """One layer call of the recurrence over ``tokens`` per sequence."""
    c = t.hs
    flops = batch * tokens * t.h * (5.0 * c * c + 4.0 * c)
    state = 2.0 * batch * t.h * c * c * 4
    io = batch * tokens * t.d * (3 * 2 + 4 + 2)
    return Dot(f"{tag}.wkv", flops, state + io, count)


def generate_dots(conf: dict, batch: int, prompt: int,
                  new: int) -> "list[Dot]":
    """Every dot of one ``generate`` call: prefill of ``batch`` prompts
    of ``prompt`` tokens, then ``new - 1`` decode steps."""
    t = dims(conf)
    L, b = t.layers, batch
    dots = _layer(t, b * prompt, L, "prefill")
    dots.append(_wkv(t, b, prompt, L, "prefill"))
    dots.append(_mm("prefill.head", b, t.d, t.vocab, 1))
    steps = new - 1
    if steps > 0:
        dots += _layer(t, b, L * steps, "decode")
        dots.append(_wkv(t, b, 1, L * steps, "decode"))
        dots.append(_mm("decode.head", b, t.d, t.vocab, steps))
    return dots


def request_flops(conf: dict, prompt: int, new: int) -> float:
    """Model operations to serve one request: its share of a batch."""
    return sum(x.flops * x.count for x in generate_dots(conf, 1, prompt, new))


def least_s(view, accept) -> float:
    """The least time of the dots whose tag ``accept`` takes, over the
    ``generate`` calls of a run's window."""
    t = view.cell.traffic
    return sum(least_time(
        [x for x in generate_dots(view.cell.conf, b, t["prompt_len"],
                                  t["new_tokens"]) if accept(x.name)],
        view.peak["bf16_flops_per_s"], view.peak["hbm_bytes_per_s"])[0]
        for b in view.served.batches)
