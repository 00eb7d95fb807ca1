"""The trace reduction on a small synthetic xplane with known answers."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import tracefile  # noqa: E402

# Times in microseconds on one clock; the window is [10, 110).
# Device 0: an op before the window; a while loop 18-42 whose body runs
# an output fusion 20-30 and a loop fusion 30-40; a convolution 100-120,
# clipped to 100-110.  Device 1: one loop fusion 10-60.
_XSPACE = """
planes {{
  id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {host}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.submit" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "bench.wait" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "unrelated" }} }}
}}
planes {{
  id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {dev0}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {module}
  }}
  {meta}
}}
planes {{
  id: 3 name: "/device:TPU:1"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {dev1}
  }}
  {meta}
}}
"""
_NAMES = {
    1: "%fusion.1 = bf16[8,4096]{1,0} fusion(bf16[8,4096]{1,0} %p), "
       "kind=kOutput, calls=%fused_computation.1",
    2: "%fusion.2 = f32[8]{0} fusion(f32[8,4096]{1,0} %x), kind=kLoop, "
       "calls=%fused_computation.2",
    3: "jit_generate",
    4: "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), "
       "condition=%c, body=%b",
    5: "%convolution.4 = f32[8,8]{1,0} convolution(f32[8,4]{1,0} %a, "
       "f32[4,8]{1,0} %b), dim_labels=bf_io->bf",
}
_META = "".join(
    f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
    for k, v in _NAMES.items())


def _ev(meta, start_us, length_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * 1000000}"
            f" duration_ps: {length_us * 1000000} }}")


def _trace():
    from jax.profiler import ProfileData
    host = " ".join([_ev(1, 10, 100), _ev(2, 40, 10), _ev(3, 60, 40),
                     _ev(4, 0, 200)])
    dev0 = " ".join([_ev(1, 0, 5), _ev(4, 18, 24), _ev(1, 20, 10),
                     _ev(2, 30, 10), _ev(5, 100, 20)])
    dev1 = _ev(2, 10, 50)
    text = _XSPACE.format(host=host, dev0=dev0, dev1=dev1, meta=_META,
                          module=_ev(3, 0, 200))
    return tracefile.read(ProfileData.from_text_proto(text))


def test_window_and_clipping():
    tr = _trace()
    assert tr.window == pytest.approx((10e-6, 110e-6))
    assert tr.window_s == pytest.approx(100e-6)
    assert sorted(tr.ops) == [0, 1]
    # The op before the window is dropped; the last one is clipped.
    assert [round(o.length * 1e6) for o in tr.ops[0]] == [24, 10, 10, 10]
    assert [o.leaf for o in tr.ops[0]] == [False, True, True, True]


def test_busy_is_the_union_of_op_intervals():
    tr = _trace()
    assert tr.busy_s(0) == pytest.approx(34e-6)       # 18-42 and 100-110
    assert tr.busy_s(1) == pytest.approx(50e-6)
    assert tr.mean_busy_s() == pytest.approx(42e-6)


def test_idle_gaps_are_named_by_the_innermost_harness_span():
    gaps = _trace().idle_gaps()
    # Device 0 is idle 10-18 and 42-100: the longest gap's middle (71)
    # lies in bench.wait; the first gap's middle (14) in no harness span
    # but the window.
    assert gaps[0][0] == "bench.wait"
    assert gaps[0][1] == pytest.approx(58e-6)
    assert gaps[1] == ["host outside harness spans", pytest.approx(8e-6)]


def test_top_ops_sum_leaves_by_instruction_over_devices():
    top = dict(_trace().top_ops())
    assert top["fusion.2 fusion kLoop f32[8]{0}"] == pytest.approx(60e-6)
    assert top["fusion.1 fusion kOutput bf16[8,4096]{1,0}"] == \
        pytest.approx(10e-6)
    assert top["convolution.4 convolution f32[8,8]{1,0}"] == \
        pytest.approx(10e-6)
    assert not any(k.startswith("while") for k in top)


def test_idle_share_reader():
    idle = harness.load_module(os.path.join(HERE, "metrics",
                                            "idle_share.py"))
    view = harness.RunView(cell=None, peak=None, served=None, trace=_trace())
    assert idle.read(view) == pytest.approx(58.0)
    assert idle.read(harness.RunView(None, None, None, None)) is None


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData
    text = _XSPACE.format(host=_ev(2, 0, 10), dev0="", dev1="", meta=_META,
                          module="")
    with pytest.raises(ValueError, match="bench.window"):
        tracefile.read(ProfileData.from_text_proto(text))
