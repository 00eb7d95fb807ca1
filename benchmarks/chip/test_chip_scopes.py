"""Device time by the program's scopes (``scopes.py``) and the readers
built on it, on synthetic traces and programs with known answers, and
on the program's own compiled ``generate``."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import scopes  # noqa: E402
import tracefile  # noqa: E402
from test_chip_trace import _trace  # noqa: E402

# The program behind _trace's op names.  fusion.1's root (an add) lies
# outside any layer, but it holds the MLP's dot and the attention
# output's smaller one; fusion.2 has no metadata and runs inside
# while.3; the convolution names its own scope.  A second program has a
# fusion.2 of another result type.
_PROGRAM = """HloModule jit__generate, entry_computation_layout={()->()}

%fused_computation.1 (param_0: bf16[8,4096], param_1: bf16[4096,4096]) -> bf16[8,4096] {
  %param_0 = bf16[8,4096]{1,0} parameter(0)
  %param_1 = bf16[4096,4096]{1,0} parameter(1)
  %dot.8 = bf16[8,4096]{1,0} dot(%param_0, %param_0), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(_generate)/decode/while/body/attn_out/dot_general"}
  %dot.7 = bf16[8,4096]{1,0} dot(%param_0, %param_1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(_generate)/decode/while/body/mlp/dot_general"}
  ROOT %add.2 = bf16[8,4096]{1,0} add(%dot.7, %dot.8), metadata={op_name="jit(_generate)/decode/while/body/add"}
}

%fused_computation.2 (param_0.1: f32[8,4096]) -> f32[8] {
  %param_0.1 = f32[8,4096]{1,0} parameter(0)
  ROOT %reduce.1 = f32[8]{0} reduce(%param_0.1, %c), dimensions={1}, to_apply=%sum
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %fusion.1 = bf16[8,4096]{1,0} fusion(%p), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(_generate)/decode/while/body/add"}
  ROOT %fusion.2 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.2
}

ENTRY %main.9 () -> () {
  %while.3 = (s32[], f32[8]{0}) while(%t), condition=%c, body=%body, metadata={op_name="jit(_generate)/decode/while"}
  ROOT %convolution.4 = f32[8,8]{1,0} convolution(%a, %b), dim_labels=bf_io->bf, metadata={op_name="jit(_generate)/prefill/while/body/qkv/dot_general"}
}
"""
_OTHER = """HloModule jit__generate, entry_computation_layout={()->()}

ENTRY %main.2 () -> () {
  ROOT %fusion.2 = f32[4]{0} fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(_generate)/decode/head/reduce_max"}
}
"""


def test_the_attribution_rule():
    progs = scopes.Programs([_PROGRAM, _OTHER])
    # 1: the fusion's dot with most operations (the MLP's, k 4096, over
    #    the attention output's, k 8), though its root is an add.
    assert progs.path("fusion.1", "bf16[8,4096]{1,0}") == "decode/mlp"
    # 2: the instruction's own op_name.
    assert progs.path("convolution.4", "f32[8,8]{1,0}") == "prefill/qkv"
    assert progs.path("while.3", "(s32[], f32[8]{0})") == "decode"
    # No metadata: nothing by rules 1 and 2 (rule 3 is the trace's).
    assert progs.path("fusion.2", "f32[8]{0}") == ""
    # The result type picks the program: the other's fusion.2 is head's.
    assert progs.path("fusion.2", "f32[4]{0}") == "decode/head"
    assert progs.path("fusion.99", "f32[8]{0}") is None
    assert progs.path("fusion.1", "f32[8]{0}") is None


def test_scope_seconds_are_self_times_that_add_up_to_busy():
    tr = _trace()
    sec = scopes.Programs([_PROGRAM, _OTHER]).seconds(tr)
    # Device 0: while.3 18-42 holds fusion.1 20-30 (rule 1: mlp) and the
    # unnamed fusion.2 30-40 (rule 3: the loop's decode); the loop's own
    # 4 us is decode's; convolution.4 100-110 names prefill/qkv.
    # Device 1: fusion.2 10-60, in no loop: unnamed.
    assert sec == {"decode/mlp": pytest.approx(10e-6),
                   "decode": pytest.approx(14e-6),
                   "prefill/qkv": pytest.approx(10e-6),
                   "unnamed": pytest.approx(50e-6)}
    assert sum(sec.values()) == pytest.approx(tr.busy_s(0) + tr.busy_s(1),
                                              rel=1e-12)


def test_ops_no_program_holds_are_unmatched():
    sec = scopes.Programs([]).seconds(_trace())
    assert sec == {"unmatched": pytest.approx(84e-6)}


# ---------------------------------------------------------------------------
# The scope readers, on a chip that runs each dot at its least time.
# ---------------------------------------------------------------------------

_CONF = {"hidden_size": 256, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 512,
         "vocab_size": 1000, "num_hidden_layers": 2}
_TRAFFIC = {"loop": "closed", "batch": 2, "prompt_len": 64,
            "new_tokens": 5}
_PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
_LAYER = {"q": "qkv", "k": "qkv", "v": "qkv", "o": "attn_out",
          "gate_up": "mlp", "down": "mlp", "qk": "attention",
          "pv": "attention", "head": "head"}
_SCOPED = ("prefill_roofline", "decode_roofline", "attention_roofline",
           "mlp_roofline", "unscoped_share")


def _least(dots):
    import counts
    return counts.least_time(dots, _PEAK["bf16_flops_per_s"],
                             _PEAK["hbm_bytes_per_s"])[0]


def _ideal(extra=(), scale=None):
    """A trace and a program in which each dot of one ``generate`` call
    runs alone in a fusion for exactly its least time (times
    ``scale[tag]``, where given), named by its step and layer scope, the
    decode dots inside the decode loop; ``extra`` adds (op_name or None,
    seconds) ops, doing no dot, to the loop."""
    import counts
    dots = counts.generate_dots(_CONF, _TRAFFIC["batch"],
                                _TRAFFIC["prompt_len"],
                                _TRAFFIC["new_tokens"])
    comps, body, ops, t, loop = [], [], [], 0.0, None
    for i, d in enumerate(dots + [None] * len(extra)):
        if d is None:
            op_name, length = extra[i - len(dots)]
        else:
            step, tag = d.name.split(".")
            op_name, length = (f"jit(_generate)/{step}/while/body/"
                               f"{_LAYER[tag]}/dot_general",
                               _least([d]) * (scale or {}).get(d.name, 1))
        if loop is None and (d is None or d.name.startswith("decode")):
            loop = t
        meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
        if d is not None:           # the dot names the fusion (rule 1)
            comps.append(
                f"%fc.{i} (p: bf16[8,8]) -> bf16[8,8] {{\n"
                f"  %p.{i} = bf16[8,8]{{1,0}} parameter(0)\n"
                f"  ROOT %dot.{i} = bf16[8,8]{{1,0}} dot(%p.{i}, %p.{i}), "
                "lhs_contracting_dims={1}, rhs_contracting_dims={0}"
                f"{meta}\n}}\n")
            meta = ""
        body.append(f"  %fusion.{i} = bf16[8,8]{{1,0}} fusion(%x), "
                    f"kind=kOutput, calls=%fc.{i}{meta}\n")
        ops.append(tracefile.Op(f"%fusion.{i} = bf16[8,8]{{1,0}} fusion("
                                "bf16[8,8]{1,0} %x), kind=kOutput", t,
                                length))
        t += length
    ops.append(tracefile.Op("%while.1 = (s32[]) while((s32[]) %t), "
                            "body=%b", loop, t - loop))
    text = ("HloModule jit__generate, entry_computation_layout={()->()}\n\n"
            + "".join(comps) + "%b (x: bf16[8,8]) -> bf16[8,8] {\n"
            + "".join(body) + "}\n\nENTRY %main () -> () {\n"
            '  ROOT %while.1 = (s32[]) while(%t), body=%b, metadata='
            '{op_name="jit(_generate)/decode/while"}\n}\n')
    trace = tracefile.Trace(window=(0.0, t),
                            ops={0: tracefile.mark_leaves(ops)}, spans=[])
    return dots, trace, text


def _view(trace, batches=(_TRAFFIC["batch"],)):
    cell = harness.Cell("ideal", 1, _CONF, _TRAFFIC, {}, [], [])
    served = harness.Served(0.0, 1.0, [], list(batches), 0, 0.0)
    return harness.RunView(cell, _PEAK, served, trace)


def _read(name, view):
    return harness.load_module(os.path.join(HERE, "metrics",
                                            name + ".py")).read(view)


@pytest.fixture
def programs(monkeypatch):
    """Serve ``compile_programs`` from the texts the test sets."""
    texts = []
    monkeypatch.setattr(scopes, "compile_programs", lambda view: texts)
    return texts


def test_scope_readers_read_100_percent_where_each_dot_runs_at_its_least(
        programs):
    _, trace, text = _ideal()
    programs.append(text)
    view = _view(trace)
    for name in _SCOPED[:4]:
        assert _read(name, view) == pytest.approx(100.0, rel=1e-9), name
        assert _read(name, view) <= 100.0 + 1e-9, name
    assert _read("unscoped_share", view) == 0.0


def test_scope_readers_known_answers(programs):
    dots, _, _ = _ideal()
    decode = _least([d for d in dots if d.name.startswith("decode.")])
    mlp = _least([d for d in dots if d.name.endswith((".gate_up",
                                                     ".down"))])
    # In the decode loop, an unnamed copy as long as decode's dots and
    # an op of the MLP, doing no dot, as long as the MLP's dots.
    _, trace, text = _ideal([
        (None, decode), ("jit(_generate)/decode/while/body/mlp/mul", mlp)])
    programs.append(text)
    view = _view(trace)
    assert _read("decode_roofline", view) == \
        pytest.approx(100.0 * decode / (2 * decode + mlp))
    assert _read("mlp_roofline", view) == pytest.approx(50.0)
    assert _read("prefill_roofline", view) == pytest.approx(100.0)
    assert _read("attention_roofline", view) == pytest.approx(100.0)
    assert _read("unscoped_share", view) == \
        pytest.approx(100.0 * decode / trace.busy_s(0))


def test_attention_roofline_leaves_out_decode_attention_read_elsewhere(
        programs):
    """Decode attention's dots run in a quarter of their least time,
    since an unnamed op of the loop reads the cache for them, as the
    layer scan's slices of the stacked cache do on the chip.  Read over
    both steps, attention would pass 100%; the reader, prefill attention
    alone, reads its dots' 100%, and no roofline passes 100%."""
    dots, _, _ = _ideal()
    kv = _least([d for d in dots if d.name in ("decode.qk", "decode.pv")])
    _, trace, text = _ideal([(None, 0.75 * kv)],
                            scale={"decode.qk": 0.25, "decode.pv": 0.25})
    programs.append(text)
    view = _view(trace)
    both = scopes.roofline(view, lambda tag: tag.endswith((".qk", ".pv")),
                           lambda p: scopes.layer_of(p) == "attention")
    assert both > 100.0
    assert _read("attention_roofline", view) == pytest.approx(100.0)
    for name in _SCOPED[:4]:
        assert _read(name, view) <= 100.0 + 1e-9, name


@pytest.mark.parametrize("share, reads", [(0.5e-3, True), (2e-3, False)])
def test_ops_no_program_holds_past_the_limit_silence_the_readers(
        programs, share, reads):
    """An op of another program (the split of a result into rows) runs
    after ``generate``'s: it is kept apart from the unnamed ops, and
    past ``UNMATCHED_LIMIT`` of busy time no scope reader reads."""
    _, trace, text = _ideal()
    programs.append(text)
    end = trace.window[1]
    length = share * end / (1 - share)
    op = tracefile.Op("%slice_bitcast_fusion = s32[8]{0} fusion(s32[4,8]"
                      "{1,0} %x), kind=kLoop", end, length)
    trace = tracefile.Trace(window=(0.0, end + length),
                            ops={0: tracefile.mark_leaves(
                                trace.ops[0] + [op])}, spans=[])
    sec = scopes.Programs(programs).seconds(trace)
    assert sec[scopes.UNMATCHED] == pytest.approx(length)
    assert scopes.UNNAMED not in sec
    view = _view(trace)
    for name in _SCOPED:
        assert (_read(name, view) is not None) == reads, name


def test_scope_readers_read_nothing_without_a_trace_or_scopes(programs):
    _, trace, text = _ideal()
    programs.append(text)
    for name in _SCOPED:
        assert _read(name, _view(None)) is None, name
        assert _read(name, _view(trace, batches=())) is None, name
    # A program that names no scopes, as before they were added.
    programs[:] = ["HloModule jit__generate, "
                   "entry_computation_layout={()->()}\n"]
    _, trace, _ = _ideal()
    for name in _SCOPED:
        assert _read(name, _view(trace)) is None, name


# ---------------------------------------------------------------------------
# The program's own compiled generate, as a traced run compiles it.
# ---------------------------------------------------------------------------

def test_compile_programs_compiles_one_program_per_batch_size_served():
    """``compile_programs`` compiles the window's program once for each
    batch size it served, with metadata in the compile cache's key only
    while it compiles; ``Programs`` maps each dot of the text to the path
    its ``op_name`` names (that the program names every dot is
    tests/test_serving.py's)."""
    import re
    import jax
    from test_chip_harness import tiny_cell
    from test_chip_reference import tiny_cfg
    cell = tiny_cell("closed")
    view = harness.RunView(cell, _PEAK, harness.Served(
        0.0, 1.0, [], [4, 4, 2], 0, 0.0), None)
    real = harness.program_config
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)

    def tiny_program_config(conf, base=None):
        return tiny_cfg(conf) if base is None else real(conf, base)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "program_config", tiny_program_config)
        texts = scopes.compile_programs(view)
    jax.clear_caches()
    assert getattr(jax.config, flag) == before
    prompt = cell.traffic["prompt_len"]
    layouts = [t.split("\n", 1)[0] for t in texts]   # the parameters
    assert [f"s32[{k},{prompt}]" in x for x in layouts
            for k in (2, 4)] == [True, False, False, True]
    progs = scopes.Programs(texts)
    dot = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\S+) dot\(.*"
                     r'op_name="([^"]*)"')
    found = [m.groups() for t in texts for line in t.splitlines()
             for m in [dot.match(line)] if m]
    assert found
    for name, typ, op_name in found:
        assert progs.path(name, typ) == scopes.scope_path(op_name)
