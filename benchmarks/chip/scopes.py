"""Device time by the program's named scopes.

The program names its work with ``jax.named_scope``: ``prefill`` and
``decode`` around the two phases of ``generate`` (the step scopes, the
decode loop included), and eight layer scopes inside them
(:data:`LAYERS`).  The compiler keeps the scopes in each instruction's
``op_name`` metadata, which the text of the compiled program shows
(``metadata={op_name="jit(_generate)/prefill/while/body/.../mlp/dot_general"}``),
while the trace names only the instruction (its name and result type).
So after the window this module compiles, as ``ServingEngine.run``
does, the ``generate`` program of each batch size the window served
(:func:`compile_programs`), and maps each op event of the trace to a
scope path through those texts, by the op's instruction name and result
type.  An op takes, in order:

1. for a fusion containing a dot, convolution or custom call, that
   op's scope (the one with most operations), so that a projection
   fused with a residual add stays in its layer;
2. otherwise, the instruction's own ``op_name`` scope;
3. otherwise, the scope of the innermost enclosing event on the device
   line (a loop), so that loop copies and weight slices land in
   ``prefill`` or ``decode``;
4. otherwise, none (:data:`UNNAMED`).

An op whose instruction name and result type no compiled program holds
goes to :data:`UNMATCHED` instead: the small programs that stack
prompts and split results, and, should the compiled programs drift from
what the window ran, every op of ``generate``.  Where that share passes
:data:`UNMATCHED_LIMIT` of busy time, the attribution is not trusted and
:func:`named` reads nothing.

The trace cannot give scopes itself: on a TPU v5e its ``XLA Ops``
events carry only their device offset and duration in their stats, no
``op_name`` or ``tf_op``.

A scope path is the step scope and the innermost layer scope, joined by
``/`` (``prefill/mlp``, ``decode`` for an op of decode in no layer).
Each instant of device time goes to the innermost event running then
(its self time), so the seconds of all paths add up to the busy time.
"""

from __future__ import annotations

import math
import re
from collections import defaultdict

STEPS = ("prefill", "decode")
LAYERS = ("embed", "norm", "qkv", "cache_update", "attention", "attn_out",
          "mlp", "head")
UNNAMED = "unnamed"
UNMATCHED = "unmatched"
UNMATCHED_LIMIT = 1e-3        # of busy time

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\((.*)$")
_TRACE_INSTR = re.compile(r"^%(\S+) = (.*?) [a-z][\w-]*\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.-]+)")
_DIMS = re.compile(r"\[([\d,]*)\]")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_LABELS = re.compile(r"dim_labels=\w+_(\w+)->")
_OPERAND = re.compile(r"%([\w.-]+)")


def scope_path(op_name: str) -> str:
    """``step/layer`` from an ``op_name``: the outermost step scope and
    the innermost layer scope it names; "" for neither."""
    parts = op_name.split("/")
    step = next((p for p in parts if p in STEPS), None)
    layer = next((p for p in reversed(parts) if p in LAYERS), None)
    return "/".join(p for p in (step, layer) if p)


def layer_of(path: str):
    """The layer scope of a path, or None."""
    last = path.rsplit("/", 1)[-1]
    return last if last in LAYERS else None


def step_of(path: str):
    """The step scope of a path, or None."""
    first = path.split("/", 1)[0]
    return first if first in STEPS else None


def _dims(hlo_type: str) -> "list[int]":
    m = _DIMS.search(hlo_type)
    return [int(x) for x in m.group(1).split(",")] if m and m.group(1) \
        else []


def _operations(opcode: str, typ: str, rest: str, types) -> float:
    """Multiply-adds of a dot or convolution from its shapes (the result
    size for a custom call): enough to rank the ops of one fusion."""
    out = math.prod(_dims(typ))
    operands = _OPERAND.findall(rest.split(")", 1)[0])
    if opcode == "dot" and operands:
        lhs = _dims(types.get(operands[0], ""))
        c = _CONTRACT.search(rest)
        if c and lhs:
            return float(out) * math.prod(
                lhs[int(x)] for x in c.group(1).split(",")
                if x and int(x) < len(lhs))
    if opcode == "convolution" and len(operands) > 1:
        rhs = _dims(types.get(operands[1], ""))
        lab = _LABELS.search(rest)
        if rhs and lab and len(lab.group(1)) == len(rhs) \
                and "o" in lab.group(1):
            return float(out) * math.prod(rhs) / max(
                rhs[lab.group(1).index("o")], 1)
    return float(out)


class Programs:
    """The text of the window's compiled programs: for each instruction
    its result type and scope path, by rules 1 and 2."""

    def __init__(self, texts):
        self.ops = defaultdict(list)      # name -> [(type, path)]
        for text in texts:
            comps, cur = {}, None
            for line in text.splitlines():
                c = _COMPUTATION.match(line)
                if c:
                    cur = comps.setdefault(c.group(1), [])
                    continue
                i = _INSTR.match(line)
                if i and cur is not None:
                    name, typ, opcode, rest = i.groups()
                    op, calls = _OP_NAME.search(rest), _CALLS.search(rest)
                    cur.append((name, typ, opcode, rest,
                                scope_path(op.group(1)) if op else "",
                                calls.group(1) if calls else None))
            types = {i[0]: i[1] for instrs in comps.values() for i in instrs}
            for instrs in comps.values():
                for name, typ, opcode, _, path, calls in instrs:
                    if opcode == "fusion" and calls:
                        inner = _heaviest(comps, calls, types, set())
                        path = path if inner is None else inner
                    self.ops[name].append((typ, path))

    def path(self, instruction: str, typ: str):
        """The path of the instruction of that result type by rules 1
        and 2; "" where it has no scope, None where no program has it."""
        for t, p in self.ops.get(instruction, ()):
            if t == typ:
                return p
        return None

    def seconds(self, trace) -> "dict[str, float]":
        """Device seconds of each scope path in ``trace``, summed over
        devices; :data:`UNNAMED` for ops in no scope, :data:`UNMATCHED`
        for ops no program holds."""
        out = defaultdict(float)
        for ops in trace.ops.values():
            _attribute(ops, self, out)
        return dict(out)


def _heaviest(comps, comp: str, types, seen):
    """The scope of the dot, convolution or custom call with most
    operations in computation ``comp`` and the fusions it calls; None if
    it has none."""
    best, most = None, -1.0
    seen.add(comp)
    for name, typ, opcode, rest, path, calls in comps.get(comp, ()):
        if opcode in ("dot", "convolution", "custom-call"):
            n = _operations(opcode, typ, rest, types)
            if n > most:
                best, most = path, n
        elif opcode == "fusion" and calls and calls not in seen:
            inner = _heaviest(comps, calls, types, seen)
            if inner is not None and best is None:
                best, most = inner, 0.0
    return best


def _attribute(ops, programs: Programs, out) -> None:
    """Add each instant of one device's ``ops`` to the path of the
    innermost op running then."""
    known = {}                            # op text -> path by rules 1, 2
    stack = []                            # running ops: [end, path]
    t = None

    def credit(entry, until):
        nonlocal t
        if until > t:
            out[entry[1] or UNNAMED] += until - t
            t = until

    for o in sorted(ops, key=lambda o: (o.start, -o.length)):
        # An op that ends within a picosecond of the next start ended
        # before it (the times are sums of floats).
        while stack and stack[-1][0] <= o.start + 1e-12:
            credit(stack[-1], stack[-1][0])
            stack.pop()
        if stack:
            credit(stack[-1], o.start)
        t = o.start if t is None else max(t, o.start)
        if o.name not in known:
            m = _TRACE_INSTR.match(o.name)
            known[o.name] = programs.path(m.group(1), m.group(2)) \
                if m else None
        path = known[o.name]
        if path is None:
            path = UNMATCHED
        elif not path and stack:
            path = stack[-1][1]           # rule 3: the enclosing loop
        stack.append([o.start + o.length, path])
    while stack:
        credit(stack[-1], stack[-1][0])
        stack.pop()


def compile_programs(view) -> "list[str]":
    """The text of the ``generate`` program of each batch size the
    window served, compiled as ``ServingEngine.run`` compiles it for the
    harness's engine.

    The compile cache's key leaves metadata out, so the program the
    window ran may have been loaded from an entry that another checkout
    (one with other scopes, or none) compiled; its text would carry that
    checkout's ``op_name``s.  So the key takes the metadata in here: the
    text is this checkout's, compiled once and cached under its own key.
    Only metadata can differ, so its instruction names are the trace's.
    """
    import jax
    import jax.numpy as jnp
    import harness
    from repro.serving.engine import lower_generate
    cell = view.cell
    t = cell.traffic
    ref = harness.reference_module(cell.conf)
    params = jax.eval_shape(lambda k: ref.program_params(cell.conf, k),
                            harness.seed_key(0))
    cfg = harness.program_config(cell.conf)
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return [lower_generate(
            cfg, params,
            {"tokens": jax.ShapeDtypeStruct((k, t["prompt_len"]),
                                            jnp.int32)},
            max_new_tokens=t["new_tokens"], temperature=0.0,
            cache_len=t["prompt_len"] + t["new_tokens"]).compile().as_text()
            for k in sorted(set(view.served.batches))]
    finally:
        jax.config.update(flag, before)


# The readers of one run share one attribution: the last trace read and
# its scope seconds.
_last = []


def named(view) -> "dict[str, float] | None":
    """Device seconds of each scope path of the run's trace, or None
    without a trace, where no op lies in a layer scope (a program that
    names no scopes), or where ops no program holds pass
    :data:`UNMATCHED_LIMIT` of busy time."""
    if view.trace is None or not view.served.batches:
        return None
    if not (_last and _last[0] is view.trace):
        _last[:] = [view.trace,
                    Programs(compile_programs(view)).seconds(view.trace)]
    sec = _last[1]
    if not any(layer_of(p) for p in sec):
        return None
    if sec.get(UNMATCHED, 0.0) > UNMATCHED_LIMIT * sum(sec.values()):
        return None
    return sec


def roofline(view, dots, paths):
    """The least time of the window's dots whose tag ``dots`` accepts,
    over the device time of the scope paths ``paths`` accepts, in
    percent; None where :func:`named` has nothing or that time is 0."""
    import counts
    sec = named(view)
    if sec is None or view.peak is None:
        return None
    busy = sum(s for p, s in sec.items() if paths(p))
    if busy <= 0:
        return None
    t = view.cell.traffic
    least = 0.0
    for b in view.served.batches:
        least += counts.least_time(
            [x for x in counts.generate_dots(view.cell.conf, b,
                                             t["prompt_len"],
                                             t["new_tokens"])
             if dots(x.name)],
            view.peak["bf16_flops_per_s"], view.peak["hbm_bytes_per_s"])[0]
    return 100.0 * least / busy
