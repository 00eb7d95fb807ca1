"""Reduction of a ``jax.profiler`` trace to device busy time and op times.

A trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes.
Its device planes are named ``/device:TPU:<n>``; their line ``XLA Ops``
holds one event per HLO instruction run on the chip, named by the
instruction's text (``%fusion.12 = bf16[8,4096]{...} fusion(...),
kind=kOutput, calls=...``), with its start and length in nanoseconds on
the host's clock.  Control flow nests: a ``while`` event spans the
events of its body.  The harness's own spans are events of the host
plane ``/host:CPU`` whose names start with ``bench.``; the span
``bench.window`` bounds the traced window.

The reduction keeps, per device, the op events inside the window.  Busy
time is the length of the union of their intervals, and the idle gaps
are what lies between.  Op times are summed over leaf events only (those
that contain no other), so that a loop is not counted with its body.
The trace gives no op category, and the compiler fuses a matmul into
fusions of several kinds, so the reduction does not try to tell dots
from other ops.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:[A-Za-z_]+:(\d+)$")


_INSTRUCTION = re.compile(r"^%(\S+) = (.*?) ([a-z][\w-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")


@dataclasses.dataclass
class Op:
    name: str         # the instruction's text, as the trace gives it
    start: float      # seconds
    length: float     # seconds
    leaf: bool = True

    @property
    def short(self) -> str:
        """``name opcode[ kind] result-type``, or the name as given."""
        m = _INSTRUCTION.match(self.name)
        if not m:
            return self.name
        kind = _KIND.search(self.name)
        return " ".join([m.group(1), m.group(3)]
                        + ([kind.group(1)] if kind else [])
                        + [m.group(2)])


@dataclasses.dataclass
class Trace:
    window: "tuple[float, float]"            # seconds on the trace clock
    ops: "dict[int, list[Op]]"               # device -> ops in the window
    spans: "list[tuple[str, float, float]]"  # host spans: name, start, end

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self, device: int) -> float:
        return sum(b - a for a, b in union(
            (o.start, o.start + o.length) for o in self.ops[device]))

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.ops) / len(self.ops)

    def top_ops(self, n: int = 10):
        """The leaf ops that took most device time, summed by
        instruction over devices: [[name, seconds], ...]."""
        tot = defaultdict(float)
        for ops in self.ops.values():
            for o in ops:
                if o.leaf:
                    tot[o.short] += o.length
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10):
        """The longest idle gaps of the first device, each named by the
        innermost harness span covering its middle: [[name, seconds]]."""
        dev = min(self.ops)
        busy = union((o.start, o.start + o.length) for o in self.ops[dev])
        edges = [self.window[0]] + [x for iv in busy for x in iv] \
            + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            mid = (a + b) / 2
            inner = [s for s in self.spans
                     if s[1] <= mid <= s[2] and s[0] != WINDOW_SPAN]
            name = (min(inner, key=lambda s: s[2] - s[1])[0] if inner
                    else "host outside harness spans")
            out.append([name, b - a])
        return out


def union(intervals):
    """Sorted, merged intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def mark_leaves(ops):
    """Sort ``ops`` by start and mark each that contains another as not
    a leaf.  Events of one line nest or follow each other, so an event
    contains another exactly when the next one starts before it ends."""
    ops.sort(key=lambda o: (o.start, -o.length))
    for a, b in zip(ops, ops[1:]):
        if b.start < a.start + a.length - 1e-12:
            a.leaf = False
    return ops


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read(profile) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Trace`."""
    spans, window = [], None
    devices = {}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = (e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                        spans.append(s)
                        if e.name == WINDOW_SPAN:
                            window = (s[1], s[2])
        elif DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev = int(DEVICE_PLANE.match(plane.name).group(1))
                    devices[dev] = mark_leaves([
                        Op(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events])
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError(f"the trace has no device line {OPS_LINE!r}")
    lo, hi = window
    ops = {d: [Op(o.name, max(o.start, lo),
                  min(o.start + o.length, hi) - max(o.start, lo), o.leaf)
               for o in v if o.start < hi and o.start + o.length > lo]
           for d, v in devices.items()}
    return Trace(window=window, ops=ops, spans=spans)


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    return read(ProfileData.from_file(find_xplane(log_dir)))
