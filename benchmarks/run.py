"""Benchmark harness — one function per paper table/figure.  Prints ``name,us_per_call,derived`` CSV rows
(us_per_call = wall time of the benchmark computation itself; derived =
the headline metric that the corresponding paper artifact reports).

Run:  PYTHONPATH=src python -m benchmarks.run [--only NAME]
"""

from __future__ import annotations

import argparse
import time

ROWS = []

#: Backend-registry name of the modelling engine pricing table6/overlap
#: ("analytical" = closed-form core.simulator, "desim" = discrete-event
#: task-graph runtime, "desim-cluster" = multi-unit contended DES;
#: aliases like "analytic" accepted).  Set by --engine.
ENGINE = "analytical"

#: Cluster width for the cluster bench and (when the selected engine
#: supports it) for the workload pricer.  Set by --units.
UNITS = 1

#: True when --units was given explicitly (the serving bench defaults
#: its cluster point to 2 units otherwise).
UNITS_SET = False

#: Serving batching policies the serving bench compares; --policy
#: restricts the sweep to one of them (or "auto").
POLICY = None

#: True when --tuned was given: serving plans resolve through the
#: per-platform tuning cache (``repro.backend.get_tuned`` dispatch)
#: instead of the untuned defaults.
TUNED = False


def require_units_support(backend_name: str, units: int) -> None:
    """Refuse a multi-unit bench row on a single-unit backend.  A bench
    that quietly prices ``units=1`` while the row is labelled ``u2``
    records a wrong baseline that every later run is then gated
    against — so this is a hard error, not a skip."""
    from repro import backend
    if units != 1 and not backend.get(backend_name).supports_units:
        raise ValueError(
            f"bench row wants units={units} but backend "
            f"{backend_name!r} models a single matrix unit; use a "
            "cluster-aware backend ('desim-cluster', 'analytical') or "
            "drop the row — refusing to silently record units=1")


def workload_sim():
    """The model-level simulator the --engine registry lookup selects
    (same signature as ``core.simulator.simulate_workload``)."""
    from repro import backend
    require_units_support(ENGINE, UNITS)
    eng = backend.get(ENGINE)
    if eng.supports_units:
        # pin the cluster width to --units (cluster backends default to
        # units=2 otherwise)
        eng = backend.get(ENGINE, units=UNITS)

    def run(unit, layers, *, fused=True):
        return eng.run_workload(layers, unit=unit, fused=fused)
    return run


def emit(name: str, us: float, derived: str):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6


# ---------------------------------------------------------------------------
# Table 2 / Eq. 1 — throughput of the configurable unit.
# ---------------------------------------------------------------------------

def bench_eq1_throughput():
    from repro.core.config import CASE_STUDY, scaling_sweep
    from repro.core.hardware import TERA
    from repro.core.precision import DataType

    def run():
        rows = []
        for cfg in [CASE_STUDY] + scaling_sweep():
            rows.append((cfg.describe(),
                         cfg.throughput(DataType.INT8) / TERA))
        return rows

    rows, us = timed(run)
    case = rows[0][1]
    emit("eq1_throughput_case_study", us, f"tops_int8={case:.3f}(paper:4.096)")
    lo = min(r[1] for r in rows)
    hi = max(r[1] for r in rows)
    emit("eq1_scaling_envelope", us, f"tops_range={lo:.2f}..{hi:.1f}"
         f"(paper:0.5..32)")


# ---------------------------------------------------------------------------
# Fig. 6 — GEMM utilization across the four CPU platforms (2 TOPS unit).
# ---------------------------------------------------------------------------

def bench_fig6_platforms():
    from repro.core.config import PLATFORM_2TOPS
    from repro.core.hardware import PLATFORMS
    from repro.core.simulator import simulate_gemm
    from repro.core.task import MatMulTask

    def run():
        out = {}
        for name, platform in PLATFORMS.items():
            utils = []
            for k in (256, 512, 1024, 2048, 4096, 8192):
                r = simulate_gemm(PLATFORM_2TOPS,
                                  MatMulTask(m=512, n=512, k=k), platform)
                utils.append(r.utilization)
            out[name] = min(utils)
        return out

    out, us = timed(run)
    worst = min(out.values())
    detail = " ".join(f"{k}={v:.3f}" for k, v in out.items())
    emit("fig6_gemm_util_4platforms", us,
         f"min_util={worst:.3f}(paper:>0.90) {detail}")


# ---------------------------------------------------------------------------
# Fig. 7 — utilization across compute/bandwidth scales, Eq.2-sized.
# ---------------------------------------------------------------------------

def bench_fig7_scaling():
    from repro.core import constraint
    from repro.core.config import MatrixUnitConfig
    from repro.core.hardware import GIGA, SHUTTLE
    from repro.core.simulator import simulate_gemm
    from repro.core.task import MatMulTask

    #: paper-style points — four bandwidth settings, each with a peak
    #: sized to the balance the paper's Fig. 7 shows (~0.8 band with the
    #: printed-Eq.2 64x64 scratchpad): (PE, K_pe bits, bandwidth GB/s).
    points = [((2, 2), 256, 8), ((2, 2), 512, 16), ((4, 4), 256, 32),
              ((4, 4), 512, 64),
              ((4, 4), 512, 48)]     # the Table-2 case study (starved)

    def run():
        paper_band, ours_band = [], []
        for (m, n), kb, bw in points:
            base = MatrixUnitConfig(m_pe=m, n_pe=n, k_pe_bits=kb,
                                    bandwidth=bw * GIGA)
            task = MatMulTask(m=512, n=512, k=4096)
            # Paper's printed Eq.2 keeps the 64x64 scratchpad.
            paper_band.append(simulate_gemm(base, task, SHUTTLE).utilization)
            # Saturating direction (beyond-paper): Eq.2 solved for >=100%.
            ms, ns = constraint.solve_scratchpad(base)
            sat = base.with_(m_scp=ms, n_scp=ns)
            ours_band.append(simulate_gemm(sat, task, SHUTTLE).utilization)
        return paper_band, ours_band

    (paper_band, ours_band), us = timed(run)
    emit("fig7_scaling_paper_eq2", us,
         "util=" + "/".join(f"{u:.2f}" for u in paper_band)
         + "(paper:~0.80)")
    emit("fig7_scaling_saturating_eq2", us,
         "util=" + "/".join(f"{u:.2f}" for u in ours_band)
         + "(beyond-paper:>0.9)")


# ---------------------------------------------------------------------------
# Fig. 8 — large-GEMM throughput vs the commercial baselines.
# ---------------------------------------------------------------------------

def bench_fig8_gemm():
    from repro.core.config import CASE_STUDY
    from repro.core.hardware import BASELINES, SHUTTLE, TERA
    from repro.core.simulator import (LayerTrace, baseline_layer_seconds,
                                      simulate_gemm)
    from repro.core.task import MatMulTask

    def run():
        task = MatMulTask(m=512, n=512, k=4096)
        ours = simulate_gemm(CASE_STUDY, task, SHUTTLE)
        ours_tops = task.flops / ours.seconds(CASE_STUDY.freq_hz) / TERA
        rel = {}
        for name, base in BASELINES.items():
            t = baseline_layer_seconds(base, LayerTrace("g", (task,)))
            rel[name] = task.flops / t / TERA
        return ours_tops, rel

    (ours_tops, rel), us = timed(run)
    detail = " ".join(f"vs_{k}={ours_tops / v:.2f}x" for k, v in rel.items())
    emit("fig8_gemm_vs_baselines", us,
         f"ours={ours_tops:.2f}TOPS {detail}(paper:>1x amx/mma,~1x sme)")


# ---------------------------------------------------------------------------
# Table 6 / Figs. 9–11 — model inference, fused vs unfused vs baselines.
# ---------------------------------------------------------------------------

def bench_table6_models():
    from benchmarks.workloads import WORKLOADS
    from repro.core.config import CASE_STUDY
    from repro.core.hardware import BASELINES
    from repro.core.simulator import baseline_workload_seconds
    simulate_workload = workload_sim()

    paper = {  # Table 6 (R, B, L) rows: (unfused, fused) speedups.
        "resnet50": {"xeon8580": (1.19, 1.57), "ibms1022": (7.16, 8.87),
                     "applem4": (3.82, 5.04)},
        "bert": {"xeon8580": (1.28, 1.57), "ibms1022": (2.72, 3.33),
                 "applem4": (1.72, 2.11)},
        "llama3": {"xeon8580": (1.87, 2.31), "ibms1022": (2.39, 3.08),
                   "applem4": (2.55, 3.16)},
    }

    for wname, build in WORKLOADS.items():
        layers = build()
        t0 = time.perf_counter()
        fused = simulate_workload(CASE_STUDY, layers, fused=True)["seconds"]
        unfused = simulate_workload(CASE_STUDY, layers,
                                    fused=False)["seconds"]
        us = (time.perf_counter() - t0) * 1e6
        for bname, base in BASELINES.items():
            tb = baseline_workload_seconds(base, layers, workload=wname)
            tb_raw = baseline_workload_seconds(base, layers)
            su_u, su_f = tb / unfused, tb / fused
            pu, pf = paper[wname][bname]
            emit(f"table6_{wname}_vs_{bname}", us,
                 f"unfused={su_u:.2f}x fused={su_f:.2f}x"
                 f"(paper:{pu:.2f}/{pf:.2f}) raw_hw={tb_raw / fused:.2f}x")
        emit(f"table6_{wname}_fusion_gain", us,
             f"fused_over_unfused={unfused / fused:.2f}x"
             f"(paper_implied:{paper[wname]['xeon8580'][1] / paper[wname]['xeon8580'][0]:.2f}x)")


# ---------------------------------------------------------------------------
# §1 overlap-contribution claim (66.7/50.9/33.6 % of gain vs Xeon).
# ---------------------------------------------------------------------------

def bench_overlap_contribution():
    from benchmarks.workloads import WORKLOADS
    from repro.core.config import CASE_STUDY
    from repro.core.hardware import XEON_8580
    from repro.core.simulator import baseline_workload_seconds
    simulate_workload = workload_sim()

    paper = {"resnet50": 66.7, "bert": 50.9, "llama3": 33.6}
    for wname, build in WORKLOADS.items():
        layers = build()
        t0 = time.perf_counter()
        fused = simulate_workload(CASE_STUDY, layers, fused=True)["seconds"]
        unfused = simulate_workload(CASE_STUDY, layers,
                                    fused=False)["seconds"]
        tb = baseline_workload_seconds(XEON_8580, layers, workload=wname)
        us = (time.perf_counter() - t0) * 1e6
        su_f, su_u = tb / fused, tb / unfused
        contrib = 100.0 * (su_f - su_u) / max(su_f - 1.0, 1e-9)
        emit(f"overlap_contribution_{wname}", us,
             f"pct_of_gain={contrib:.1f}(paper:{paper[wname]:.1f})")


# ---------------------------------------------------------------------------
# Discrete-event task-graph runtime (repro.sim) — cross-check + claims.
# ---------------------------------------------------------------------------

def bench_desim():
    from benchmarks.workloads import llama3_1b_layers
    from repro.core.config import CASE_STUDY, PLATFORM_2TOPS
    from repro.core.hardware import BOOM, KUNMINGHU, PLATFORMS
    from repro.core.simulator import simulate_gemm, simulate_workload
    from repro.core.task import MatMulTask
    from repro.sim.lower import desim_gemm, desim_workload, exposed_dispatch

    # ≥90% matrix-unit utilization for a large int8 GEMM, all 4 platforms,
    # now derived from per-resource timelines instead of a closed form.
    task = MatMulTask(m=512, n=512, k=8192)

    def run_util():
        out = {}
        for name, p in PLATFORMS.items():
            r = desim_gemm(PLATFORM_2TOPS, task, p)
            a = simulate_gemm(PLATFORM_2TOPS, task, p)
            out[name] = (r.matrix_utilization, r.cycles / a.cycles)
        return out

    out, us = timed(run_util)
    worst = min(u for u, _ in out.values())
    drift = max(abs(rel - 1.0) for _, rel in out.values())
    emit("desim_gemm_util_4platforms", us,
         f"min_util={worst:.3f}(paper:>0.90) max_vs_analytic={drift:.1%}")

    # Dispatch-queue backpressure: CSR mailbox (Kunminghu) vs RoCC (BOOM)
    # on a dispatch-dominated tiny-tile stream (paper Table 3 regime).
    tiny_unit = PLATFORM_2TOPS.with_(m_scp=16, n_scp=16)
    tiny = MatMulTask(m=128, n=128, k=32)
    (csr, rocc), us = timed(lambda: (
        exposed_dispatch(tiny_unit, tiny, KUNMINGHU),
        exposed_dispatch(tiny_unit, tiny, BOOM)))
    emit("desim_exposed_dispatch_csr_vs_rocc", us,
         f"csr={csr:.0f}cyc rocc={rocc:.0f}cyc ratio={csr / max(rocc, 1):.1f}x")

    # ≥30% overlap-attributed speedup, fused vs unfused TaskGraph on the
    # Llama-style stack, cross-checked against the analytical engine.
    def run_overlap():
        layers = llama3_1b_layers(seq=1024)
        f = desim_workload(CASE_STUDY, layers, fused=True)
        u = desim_workload(CASE_STUDY, layers, fused=False)
        af = simulate_workload(CASE_STUDY, layers, fused=True)
        return u["cycles"] / f["cycles"], f["cycles"] / af["cycles"], \
            f["matrix_utilization"]

    (gain, rel, util), us = timed(run_overlap)
    emit("desim_llama_overlap_gain", us,
         f"fused_over_unfused={gain:.2f}x(paper:>1.30) "
         f"vs_analytic={rel:.3f} matrix_util={util:.3f}")


# ---------------------------------------------------------------------------
# Cluster scaling (repro.sim cluster topology + desim-cluster backend).
# ---------------------------------------------------------------------------

def bench_cluster():
    """Weak scaling on the paper GEMM regime (512 rows × 512 × 8192 per
    unit, int8) across 1..max(UNITS, 4) matrix units sharing the memory
    loader, plus a fixed-total-bandwidth sweep that exposes where the
    shared loader saturates."""
    from repro.core.config import PLATFORM_2TOPS
    from repro.core.hardware import GIGA, SHUTTLE
    from repro.core.task import MatMulTask
    from repro.sim import (ClusterTopology, build_gemm_graph,
                           partition_graph, simulate_cluster)

    unit = PLATFORM_2TOPS
    sweep = sorted({1, 2, 4, max(UNITS, 1)})

    def weak(n_units, total_bandwidth=None):
        g, _ = build_gemm_graph(
            MatMulTask(m=512 * n_units, n=512, k=8192), unit.m_scp,
            unit.n_scp)
        part = partition_graph(g, n_units, "row-panel")
        topo = ClusterTopology(n_units=n_units, unit=unit,
                               platform=SHUTTLE,
                               total_bandwidth=total_bandwidth)
        return simulate_cluster(part.graph, topo)

    base = None
    for n in sweep:
        r, us = timed(lambda n=n: weak(n))
        base = base if base is not None else r.cycles
        emit(f"cluster_weak_u{n}", us,
             f"agg_util={r.aggregate_matrix_utilization:.3f}(goal:>0.85) "
             f"loader_util={r.loader_utilization:.3f} "
             f"contention={r.loader_contention():.2f} "
             f"eff={base / r.cycles:.3f}")

    # Strong bandwidth pressure: the pool stays at one unit's channel.
    for n in sweep:
        r, us = timed(lambda n=n: weak(n, total_bandwidth=unit.bandwidth))
        emit(f"cluster_weak_fixedbw_u{n}", us,
             f"agg_util={r.aggregate_matrix_utilization:.3f} "
             f"loader_util={r.loader_utilization:.3f} "
             f"(shared {unit.bandwidth / GIGA:.0f} GB/s pool)")


# ---------------------------------------------------------------------------
# Serving scheduler: batching policies priced on cluster timelines.
# ---------------------------------------------------------------------------

def serving_queue(n_requests: int = 6, max_batch: int = 2,
                  arrival_gap: float = 0.0):
    """The canonical serving bench queue: a yi-6b-reduced engine with
    ``n_requests`` prompts of 64 + 32·i tokens (deterministic key-0
    contents), shared by this harness and ``benchmarks/record.py`` so
    the tracked ``BENCH_serving.json`` prices exactly the workload the
    CSV bench prints."""
    import jax
    from repro.configs.registry import get_config
    from repro.serving.engine import ServingEngine

    cfg = get_config("yi-6b", reduced=True)
    eng = ServingEngine(cfg, params=None, max_batch=max_batch,
                        cache_len=256)
    key = jax.random.PRNGKey(0)
    for i in range(n_requests):
        key, sub = jax.random.split(key)
        eng.submit(jax.random.randint(sub, (64 + 32 * i,), 0,
                                      cfg.vocab_size),
                   arrival_time=arrival_gap * i)
    return cfg, eng


def concrete_policies() -> "list[str]":
    """Registered non-meta batching policies — the sweepable set
    (``auto-slo`` wraps the sweep itself and is benched separately by
    the online loop)."""
    from repro.serving.scheduler import POLICIES
    return [n for n, c in POLICIES.items() if not getattr(c, "meta", False)]


def bench_serving():
    """TTFT p50/p99 + inter-token latency + aggregate matrix utilization
    per batching policy on a Llama-style config (yi-6b reduced, 6
    requests), priced by the contention-aware analytical closed form —
    single unit and the ``--units`` cluster (default 2), with both
    chained and relaxed-overlap lowerings on the cluster point."""
    from repro.serving.scheduler import schedule_metrics

    cfg, eng = serving_queue()
    cluster = UNITS if UNITS_SET else 2
    sweep = (1,) if cluster == 1 else (1, cluster)
    policies = [POLICY] if POLICY else concrete_policies() + ["auto"]
    for pol in policies:
        for u in sweep:
            # chained on one unit (relaxed buys nothing there); both
            # lowerings on the cluster point.  "auto" sweeps internally.
            overlaps = ("chained",) if (u == 1 or pol == "auto") \
                else ("chained", "relaxed")
            for ov in overlaps:
                def run(pol=pol, u=u, ov=ov):
                    sched = eng.plan(max_new_tokens=16, units=u,
                                     policy=pol, overlap=ov, tuned=TUNED)
                    return sched, schedule_metrics(sched, cfg.n_layers,
                                                   "analytical")

                (sched, m), us = timed(run)
                tag = f"serving_{pol}_u{u}" + \
                    ("_relaxed" if ov == "relaxed" else "")
                emit(tag, us,
                     f"policy={sched.policy} "
                     f"overlap={sched.overlap} "
                     f"ttft_p50={m['ttft_p50']:.0f} "
                     f"ttft_p99={m['ttft_p99']:.0f} "
                     f"itl_p50={m['itl_p50']:.0f} "
                     f"agg_matrix_util={m['matrix_utilization']:.3f} "
                     f"makespan={m['makespan']:.0f}")


# ---------------------------------------------------------------------------
# Online closed-loop serving: sustained-load QPS sweep + saturation knee.
# ---------------------------------------------------------------------------

#: the canonical online-bench traffic shape (fixed-seed Poisson over the
#: serving queue's prompt lengths) — shared with ``benchmarks/record.py``
#: so the tracked rows price exactly what this CSV bench prints.
ONLINE_TRAFFIC = dict(n_requests=6, seed=0, prompt_lengths=(64, 96, 128))
ONLINE_ENGINE = dict(max_batch=2, max_new_tokens=8,
                     execute_backend="analytical")


def bench_online():
    """Closed-loop sustained load per policy: offered-QPS sweep (TTFT /
    ITL / goodput curves) plus the saturation sweep locating where each
    policy's goodput collapses (``repro.serving.online``).  Fixed-seed
    Poisson arrivals, analytical epoch execution — deterministic and
    fast enough for CI; ``--policy`` restricts the sweep."""
    from repro.configs.registry import get_config
    from repro.serving.online import find_saturation, qps_sweep

    cfg = get_config("yi-6b", reduced=True)
    policies = ([POLICY] if POLICY and POLICY != "auto"
                else concrete_policies())
    for pol in policies:
        rows, us = timed(lambda pol=pol: qps_sweep(
            cfg, [1e4, 1e5, 1e6], policy=pol,
            **ONLINE_TRAFFIC, **ONLINE_ENGINE))
        for r in rows:
            emit(f"online_{pol}_q{r['offered_qps']:.0e}", us / len(rows),
                 f"ttft_p50={r['ttft_p50']:.0f} "
                 f"ttft_p99={r['ttft_p99']:.0f} "
                 f"itl_p50={r['itl_p50']:.0f} "
                 f"goodput={r['goodput_qps']:.0f}req/s "
                 f"epochs={r['epochs']:.0f} "
                 f"preempt={r['preemptions']:.0f}")
        sat, us = timed(lambda pol=pol: find_saturation(
            cfg, start_qps=1e4, factor=4.0, max_points=6, policy=pol,
            **ONLINE_TRAFFIC, **ONLINE_ENGINE))
        emit(f"online_{pol}_saturation", us,
             f"knee_qps={sat['knee_qps']:.0f} "
             f"peak_goodput={sat['peak_goodput_qps']:.0f}req/s "
             f"saturated={sat['saturated']}")


# ---------------------------------------------------------------------------
# Table 7 — area/power.
# ---------------------------------------------------------------------------

def bench_table7_area():
    from repro.core.area import estimate
    from repro.core.config import CASE_STUDY

    ap, us = timed(lambda: estimate(CASE_STUDY))
    emit("table7_area_power", us,
         f"mm2={ap.total_mm2:.3f}(paper:0.531) W={ap.total_w:.3f}"
         f"(paper:1.506)")
    sat, us2 = timed(lambda: estimate(CASE_STUDY.with_(m_scp=128,
                                                       n_scp=128)))
    emit("table7_area_saturating_variant", us2,
         f"mm2={sat.total_mm2:.3f} (+{sat.total_mm2 - ap.total_mm2:.3f} "
         f"buys >95% util)")


# ---------------------------------------------------------------------------
# Pallas kernel microbenchmark (interpret mode: correctness-grade timing).
# ---------------------------------------------------------------------------

def bench_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.fusion import Epilogue
    from repro.kernels.matmul.ops import fused_matmul
    from repro.kernels.matmul.ref import fused_matmul_ref

    a = jax.random.normal(jax.random.PRNGKey(0), (256, 512), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(1), (512, 512), jnp.bfloat16)
    ep = Epilogue(activation="gelu", out_dtype=jnp.bfloat16)
    out = fused_matmul(a, b, epilogue=ep, block_shape=(128, 128, 128))
    out.block_until_ready()
    t0 = time.perf_counter()
    out = fused_matmul(a, b, epilogue=ep, block_shape=(128, 128, 128))
    out.block_until_ready()
    us = (time.perf_counter() - t0) * 1e6
    ref = fused_matmul_ref(a, b, epilogue=ep)
    r = np.asarray(ref, np.float32)
    err = float(np.abs(np.asarray(out, np.float32) - r).max()
                / (np.abs(r).max() + 1e-9))
    emit("kernel_fused_matmul_interpret", us, f"rel_err={err:.2e}")


# ---------------------------------------------------------------------------
# Tuned dispatch: the autotuner's measured end-to-end win.
# ---------------------------------------------------------------------------

#: platforms the tune bench prices (two distinct dispatch models —
#: RoCC in-order and CSR OoO — is the acceptance bar; --only tune with
#: all four is a cache-regeneration sanity sweep, not the default).
TUNE_PLATFORMS = ("shuttle", "kunminghu")


def bench_tune():
    """Tuned vs untuned cluster-DES makespan of the canonical
    Llama-style decode regime per platform, with the epilogue-fusion
    contribution isolated (tuned-unfused / tuned-fused)."""
    from repro.tune.regime import measure_decode_regime

    for plat in TUNE_PLATFORMS:
        m, us = timed(lambda plat=plat: measure_decode_regime(plat))
        emit(f"tune_decode_{plat}", us,
             f"tuned={m['tuned']:.0f} untuned={m['untuned']:.0f} "
             f"tuned_speedup={m['tuned_speedup']:.3f} "
             f"fusion_speedup={m['fusion_speedup']:.3f} "
             f"end_to_end_speedup={m['speedup']:.3f}")


BENCHES = {
    "eq1": bench_eq1_throughput,
    "fig6": bench_fig6_platforms,
    "fig7": bench_fig7_scaling,
    "fig8": bench_fig8_gemm,
    "table6": bench_table6_models,
    "overlap": bench_overlap_contribution,
    "desim": bench_desim,
    "cluster": bench_cluster,
    "serving": bench_serving,
    "online": bench_online,
    "table7": bench_table7_area,
    "kernels": bench_kernels,
    "tune": bench_tune,
}


def main() -> None:
    global ENGINE, UNITS, UNITS_SET, POLICY, TUNED
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, metavar="NAME[,NAME...]",
                    help="run only the named bench(es), comma-separated; "
                         "an unknown name errors with the known list")
    ap.add_argument("--engine", default="analytical",
                    help="repro.backend registry name of the modelling "
                         "engine for table6/overlap (aliases accepted): "
                         "'analytical' (closed form), 'desim' (the "
                         "discrete-event TaskGraph runtime) or "
                         "'desim-cluster' (multi-unit contended DES; "
                         "combine with --units)")
    ap.add_argument("--units", type=int, default=None,
                    help="matrix units for the cluster bench sweep, the "
                         "serving bench's cluster point (default 2) and, "
                         "when --engine supports it (desim-cluster, "
                         "analytical), the workload pricer")
    ap.add_argument("--policy", default=None,
                    choices=("full-prefill", "chunked-prefill",
                             "decode-priority", "auto"),
                    help="restrict the serving/online benches to one "
                         "batching policy (default: sweep all concrete "
                         "policies + auto)")
    ap.add_argument("--tuned", action="store_true",
                    help="resolve serving plans through the per-platform "
                         "tuning cache (repro.backend.get_tuned dispatch) "
                         "instead of the untuned defaults")
    args = ap.parse_args()
    only = None
    if args.only:
        only = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = sorted(set(only) - set(BENCHES))
        if unknown:
            ap.error(f"unknown bench name(s): {', '.join(unknown)}; "
                     f"known benches: {', '.join(BENCHES)}")
    from repro import backend
    try:
        ENGINE = backend.resolve(args.engine)
    except KeyError as e:
        ap.error(str(e))
    if args.units is not None and args.units < 1:
        ap.error(f"--units must be >= 1, got {args.units}")
    UNITS_SET = args.units is not None
    UNITS = args.units if UNITS_SET else 1
    POLICY = args.policy
    TUNED = args.tuned
    probe = backend.get(ENGINE)
    if UNITS != 1 and not probe.supports_units and only != ["cluster"]:
        ap.error(f"--units {UNITS} needs a cluster-aware --engine "
                 "('desim-cluster'), or --only cluster")
    if not probe.models_time:
        ap.error(f"--engine {ENGINE!r} executes numbers but does not "
                 "model time; pick one of "
                 f"{[n for n in backend.available() if backend.get(n).models_time]}")
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if only is not None and name not in only:
            continue
        fn()


if __name__ == "__main__":
    main()
