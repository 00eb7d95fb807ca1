"""The chip benchmark's harness: one cell, one seed, one run.

``run.py`` is the command; this module does the work, so that tests can
drive a run without a chip.  A cell is found by name in
``BENCHMARK.json``: its configuration file (``configs/``), its traffic
mix (``traffic/<traffic>.json``), its correctness limits
(``limits/<cell>.json``), the family's weights and reference
(``reference/<family>.py``) and a reader for each per-layer metric
(``metrics/<name>.py``, else ``metrics/<name before the first dot>.py``).
Adding a cell adds files and entries; it edits none.

A run: check the device, draw the weights on it from the seed, build the
program's ``ServingEngine``, serve every batch shape of the mix once
(set-up), then serve the mix for ``seconds`` (the window) through
``ServingEngine.submit`` and ``run``.  After the window: read peak
memory, reduce the trace, free the program's state, and compare a
sample of the served tokens with the float32 reference.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

import traffic as traffic_lib

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class Refused(Exception):
    """The run cannot be made here: wrong device, missing program or
    files, or a configuration that does not match the program."""


def load_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise Refused(f"{os.path.relpath(path, ROOT)} is missing") from None


def load_module(path: str):
    name = "chipbench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Cells.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict              # the configuration file
    traffic: dict           # the traffic mix
    limits: dict            # the correctness limits
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def resolve(bench: dict, name: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; "
                      f"known: {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(name=name, chips=w["chips"],
                conf=load_json(os.path.join(ROOT, entry["file"])),
                traffic=load_json(os.path.join(HERE, "traffic",
                                               w["traffic"] + ".json")),
                limits=load_json(os.path.join(HERE, "limits",
                                              name + ".json")),
                end_to_end=e2e, per_layer=per_layer)


def program_config(conf: dict, base=None):
    """The program's ``ArchConfig`` for a configuration file.

    ``base`` is the registry's config (by the file's ``registry`` name
    unless given).  Every width must agree with the file; the depth, the
    norm's epsilon and the rotary base are taken from the file, since
    the program can run them as stated."""
    import jax.numpy as jnp
    if base is None:
        from repro.configs.registry import get_config
        base = get_config(conf["registry"])
    want = {
        "hidden_size": base.d_model, "intermediate_size": base.d_ff,
        "num_attention_heads": base.n_heads,
        "num_key_value_heads": base.n_kv_heads,
        "head_dim": base.head_dim, "vocab_size": base.padded_vocab,
        "family": base.family, "hidden_act": base.mlp_activation,
        "tie_word_embeddings": base.tie_embeddings,
        "attention_bias": base.qkv_bias,
        "torch_dtype": jnp.dtype(base.dtype).name}
    have = dict(conf)
    have.setdefault("head_dim",
                    conf["hidden_size"] // conf["num_attention_heads"])
    wrong = {k: (have.get(k), v) for k, v in want.items()
             if have.get(k) != v}
    if not base.mlp_glu:
        wrong["mlp_glu"] = (True, False)
    if wrong:
        raise Refused(f"{conf['name']}: the file and the program's "
                      f"{base.name!r} differ (file, program): {wrong}")
    return base.with_(n_layers=conf["num_hidden_layers"],
                      rms_eps=float(conf["rms_norm_eps"]),
                      rope_theta=float(conf["rope_theta"]))


def reference_module(conf: dict):
    return load_module(os.path.join(HERE, "reference",
                                    conf["family"] + ".py"))


def reader(metric: str):
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            return load_module(path).read
    raise Refused(f"no reader for per-layer metric {metric!r} under "
                  "benchmarks/chip/metrics/")


# ---------------------------------------------------------------------------
# The device.
# ---------------------------------------------------------------------------

def check_device(chips: int):
    """The devices and their peaks; refuses a device that the peaks
    table does not know (a CPU among them) or too few chips."""
    import jax
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform == "cpu" or kind not in peaks:
        raise Refused(f"no accelerator this benchmark knows: JAX sees "
                      f"{len(devs)} {devs[0].platform} device(s) of kind "
                      f"{kind!r}; peaks.json knows {sorted(peaks)}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX sees "
                      f"{len(devs)}")
    return devs[:chips], peaks[kind]


def seed_key(seed: int):
    """A JAX key from any whole number (``PRNGKey`` would drop the bits
    above 32)."""
    import jax.numpy as jnp
    return jnp.asarray(np.random.SeedSequence(seed).generate_state(2),
                       jnp.uint32)


def open_cell(name: str):
    """Everything a command needs before it touches the program: the
    cell by name, the compile cache in the checkout, the devices and
    their peaks, and the program's config.  Refuses outside a checkout of
    the program, and on a device ``peaks.json`` does not know."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise Refused("the program (src/repro) is not in this checkout")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cell = resolve(load_json(os.path.join(ROOT, "BENCHMARK.json")), name)
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices, peak = check_device(cell.chips)
    return cell, program_config(cell.conf), devices, peak


# ---------------------------------------------------------------------------
# Serving.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    due: float              # seconds after the window's start
    prompt: np.ndarray
    done: float = 0.0
    out: object = None      # the served tokens (device array, then numpy)


@dataclasses.dataclass
class Served:
    """What a window served, on the host's clock (seconds)."""
    start: float
    end: float
    requests: list
    batches: list           # requests per generate call
    compiles: int           # backend compiles inside the window
    late_s: float           # longest oversleep of the open-loop generator

    @property
    def window_s(self) -> float:
        return self.end - self.start


class _CompileCounter:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_a, **_k):
        if name == self.EVENT:
            self.n += 1


class Session:
    """The program under test, set up for one cell and one seed."""

    def __init__(self, cell: Cell, cfg, seed: int):
        import jax
        from repro.serving.engine import ServingEngine
        self.cell, self.seed = cell, seed
        t = cell.traffic
        self.prompt_len, self.new = t["prompt_len"], t["new_tokens"]
        self.max_batch = t.get("max_batch", t.get("batch"))
        self.vocab = cell.conf["vocab_size"]
        self.ref = reference_module(cell.conf)
        self.key = seed_key(seed)
        self.params = jax.block_until_ready(
            self.ref.program_params(cell.conf, self.key))
        self.engine = ServingEngine(cfg, self.params,
                                    max_batch=self.max_batch,
                                    cache_len=self.prompt_len + self.new)
        self.compiles = _CompileCounter()

    def batch_sizes(self):
        if self.cell.traffic["loop"] == "closed":
            return [self.cell.traffic["batch"]]
        return list(range(1, self.max_batch + 1))

    def serve(self, prompts):
        """Submit ``prompts`` and run them to completion; returns the
        served tokens."""
        import jax
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("bench.submit"):
            for p in prompts:
                self.engine.submit(p)
        with TraceAnnotation("bench.run"):
            outs = self.engine.run(max_new_tokens=self.new)
        with TraceAnnotation("bench.wait"):
            return jax.block_until_ready(outs)

    def warm_up(self):
        """Serve every batch shape of the mix once: compiles (or loads
        from the cache) every program the window will run."""
        for k in self.batch_sizes():
            self.serve(traffic_lib.warm_up_prompts(
                self.seed, k, self.prompt_len, self.vocab))

    def window(self, seconds: float) -> Served:
        from jax.profiler import TraceAnnotation
        loop = self.cell.traffic["loop"]
        before = self.compiles.n
        with TraceAnnotation("bench.window"):
            if loop == "closed":
                served = self._closed(seconds)
            elif loop == "open":
                served = self._open(seconds)
            else:
                raise Refused(f"unknown loop {loop!r}")
        served.compiles = self.compiles.n - before
        for r in served.requests:
            r.out = np.asarray(r.out)
        return served

    def _closed(self, seconds):
        from jax.profiler import TraceAnnotation
        b, reqs, batches = self.cell.traffic["batch"], [], []
        t0 = time.perf_counter()
        k = 0
        while True:
            with TraceAnnotation("bench.prompts"):
                ps = traffic_lib.prompts(self.seed, k, b, self.prompt_len,
                                         self.vocab)
            t = time.perf_counter()
            outs = self.serve(ps)
            done = time.perf_counter()
            batches.append(b)
            reqs += [Request(t - t0, p, done - t0, o)
                     for p, o in zip(ps, outs)]
            k += 1
            if done - t0 >= seconds:
                return Served(t0, done, reqs, batches, 0, 0.0)

    def _open(self, seconds):
        from jax.profiler import TraceAnnotation
        t = self.cell.traffic
        due = traffic_lib.arrival_times(t["schedule_seed"], t["rate_per_s"],
                                        seconds)
        ps = traffic_lib.prompts(self.seed, 0, len(due), self.prompt_len,
                                 self.vocab)
        reqs = [Request(float(d), p) for d, p in zip(due, ps)]
        batches, late = [], 0.0
        t0 = time.perf_counter()
        i = 0
        while i < len(reqs):
            now = time.perf_counter() - t0
            if reqs[i].due > now:
                with TraceAnnotation("bench.idle"):
                    time.sleep(reqs[i].due - now)
                late = max(late, time.perf_counter() - t0 - reqs[i].due)
                continue
            j = i
            while (j < len(reqs) and j - i < self.max_batch
                   and reqs[j].due <= now):
                j += 1
            chunk = reqs[i:j]
            outs = self.serve([r.prompt for r in chunk])
            done = time.perf_counter()
            batches.append(len(chunk))
            for r, o in zip(chunk, outs):
                r.done, r.out = done - t0, o
            i = j
        return Served(t0, t0 + max(r.done for r in reqs), reqs, batches,
                      0, late)

    def free(self):
        """Drop the program's state so that the reference has the chip."""
        import jax
        self.engine = None
        for a in jax.tree.leaves(self.params):
            a.delete()
        self.params = None
        gc.collect()

    def sample(self, served: Served):
        """The requests the correctness check compares: drawn from the
        seed, with a longest request among them."""
        reqs = served.requests
        k = self.cell.limits["sample_requests"]
        idx = traffic_lib.sample(self.seed, len(reqs), k)
        longest = max(range(len(reqs)), key=lambda i: len(reqs[i].out))
        if longest not in idx:
            idx[-1] = longest
        return [reqs[i] for i in idx]

    def gaps(self, reqs, control: bool = False):
        fn = self.ref.control_gaps if control else self.ref.served_gaps
        return fn(self.cell.conf, self.key,
                  np.stack([r.prompt for r in reqs]),
                  np.stack([r.out for r in reqs]))


def failed_requests(served: Served, new: int, vocab: int) -> int:
    return sum(1 for r in served.requests
               if r.out is None or r.out.shape != (new,)
               or not ((r.out >= 0) & (r.out < vocab)).all())


# ---------------------------------------------------------------------------
# Metrics.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader is given."""
    cell: Cell
    peak: dict
    served: Served
    trace: object           # tracefile.Trace, or None


def end_to_end(cell: Cell, served: Served, setup_s: float) -> dict:
    reqs = served.requests
    lat = [r.done - r.due for r in reqs]
    toks = sum(len(r.prompt) + len(r.out) for r in reqs)
    values = {
        "setup_s": setup_s,
        "tok_s": toks / served.window_s,
        # run() returns every token of a batch at once, so the first
        # token reaches the caller when the batch completes.
        "ttft_p90_s": traffic_lib.quantile(lat, 0.9),
        "latency_p90_s": traffic_lib.quantile(lat, 0.9),
    }
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise Refused(f"the harness does not measure {m['name']!r}")
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def per_layer(view: RunView) -> dict:
    out = {}
    for m in view.cell.per_layer:
        v = reader(m["name"])(view)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------

def run(cell: Cell, cfg, seed: int, seconds: float, trace: bool,
        t_start: float, devices=None, peak=None) -> dict:
    """One run of ``cell``; returns the result object.  ``devices`` and
    ``peak`` come from :func:`check_device`; a test passes its own."""
    import jax
    sess = Session(cell, cfg, seed)
    sess.warm_up()
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(log_dir)
    setup_s = time.perf_counter() - t_start
    served = sess.window(seconds)
    tr = None
    if trace:
        jax.profiler.stop_trace()
        import tracefile
        tr = tracefile.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
    stats = [d.memory_stats() or {} for d in (devices or [])]
    mem_peak = max((s.get("peak_bytes_in_use", 0) for s in stats),
                   default=None)
    failed = failed_requests(served, sess.new, sess.vocab)
    if trace:
        metrics = per_layer(RunView(cell, peak, served, tr))
    else:
        metrics = end_to_end(cell, served, setup_s)
    sess.free()
    t = time.perf_counter()
    sample = sess.sample(served)
    gap = float(sess.gaps(sample).max())
    ref_s = time.perf_counter() - t

    limit = cell.limits["greedy_gap"]
    checks = {
        "failed_requests": {"value": failed, "limit": 0},
        "greedy_gap": {"value": gap, "limit": limit},
    }
    correct = failed == 0 and gap <= limit
    dev = devices[0] if devices else jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices or [dev]), "memory_peak_bytes": mem_peak}
    result = {"correct": correct, "attempted": len(served.requests),
              "failed": failed, "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.mean_busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}
    result["checks"] = checks
    note = {"cell": cell.name, "seed": seed, "window_s": served.window_s,
            "requests": len(served.requests),
            "batches": len(served.batches),
            "compiles_in_window": served.compiles,
            "generator_late_s": served.late_s,
            "sampled_requests": len(sample),
            "sampled_tokens": int(sum(len(r.out) for r in sample)),
            "reference_s": ref_s}
    print("chipbench: " + json.dumps(note), file=sys.stderr, flush=True)
    return result
