"""Cluster-scale DES + sharded execution: multi-unit topology, graph
partitioning, shared-loader contention, and cross-backend parity of the
partitioned graph (desim-cluster timelines == sharded/jax numbers)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import backend
from repro.core.config import CASE_STUDY, PLATFORM_2TOPS
from repro.core.fusion import Epilogue, cute_matmul
from repro.core.hardware import SHUTTLE
from repro.core.simulator import LayerTrace
from repro.core.task import MatMulTask
from repro.sim import (ClusterTopology, Granularity, build_gemm_graph,
                       chrome_trace, dump_chrome_trace, partition_graph,
                       simulate_cluster, simulate_graph, workload_to_graph)
from repro.sim.resources import BandwidthResource, EventLoop


def int8_pair(key, m, n, k):
    ka, kb = jax.random.split(key)
    return (jax.random.randint(ka, (m, k), -8, 8, jnp.int8),
            jax.random.randint(kb, (k, n), -8, 8, jnp.int8))


# ---------------------------------------------------------------------------
# The shared-bandwidth loader.
# ---------------------------------------------------------------------------

class TestBandwidthResource:
    def test_fair_share_splits_bandwidth(self):
        loop = EventLoop()
        bw = BandwidthResource(loop, "l", policy="fair")
        ends = {}
        bw.transfer(100, "a", then=lambda: ends.setdefault("a", loop.now))
        bw.transfer(100, "b", then=lambda: ends.setdefault("b", loop.now))
        loop.run()
        # two equal flows at half rate each: both finish at 200
        assert ends == {"a": 200.0, "b": 200.0}
        assert bw.busy_cycles() == pytest.approx(200.0)

    def test_fair_share_staggered_arrival(self):
        loop = EventLoop()
        bw = BandwidthResource(loop, "l", policy="fair")
        ends = {}
        bw.transfer(100, "a", then=lambda: ends.setdefault("a", loop.now))
        loop.at(50, lambda: bw.transfer(
            100, "b", then=lambda: ends.setdefault("b", loop.now)))
        loop.run()
        # a: 50 alone + 50 work at half rate -> 150; b: 50 shared + 50 alone
        assert ends["a"] == pytest.approx(150.0)
        assert ends["b"] == pytest.approx(200.0)
        # per-flow spans overlap; union busy does not double count
        assert bw.busy_cycles() == pytest.approx(200.0)
        demand = sum(e - s for s, e, _ in bw.intervals)
        assert demand == pytest.approx(150.0 + 150.0)

    def test_fcfs_serialises(self):
        loop = EventLoop()
        bw = BandwidthResource(loop, "l", policy="fcfs")
        ends = {}
        bw.transfer(100, "a", then=lambda: ends.setdefault("a", loop.now))
        bw.transfer(100, "b", then=lambda: ends.setdefault("b", loop.now))
        loop.run()
        assert ends == {"a": 100.0, "b": 200.0}
        assert bw.busy_cycles() == pytest.approx(200.0)

    def test_bad_policy_rejected(self):
        with pytest.raises(ValueError, match="policy"):
            BandwidthResource(EventLoop(), "l", policy="lifo")
        with pytest.raises(ValueError):
            ClusterTopology(n_units=2, loader_policy="lifo")
        with pytest.raises(ValueError):
            ClusterTopology(n_units=0)


# ---------------------------------------------------------------------------
# Graph partitioning.
# ---------------------------------------------------------------------------

class TestPartition:
    def _gemm_graph(self, m=256, n=256, k=512, **kw):
        g, _ = build_gemm_graph(MatMulTask(m=m, n=n, k=k), 64, 64, **kw)
        return g

    def test_row_panel_contiguous_spans(self):
        p = partition_graph(self._gemm_graph(), 4, "row-panel")
        spans = p.spans["gemm"]
        assert spans == [(0, 64), (64, 128), (128, 192), (192, 256)]
        assert p.balanced("gemm")
        for node in p.graph.matmul_nodes():
            lo, hi = spans[node.unit]
            assert lo <= node.tile.m0 < hi

    def test_output_tile_shards_columns(self):
        p = partition_graph(self._gemm_graph(), 2, "output-tile")
        for node in p.graph.matmul_nodes():
            lo, hi = p.spans["gemm"][node.unit]
            assert lo <= node.tile.n0 < hi

    def test_single_unit_is_identity_placement(self):
        g = self._gemm_graph()
        p = partition_graph(g, 1, "row-panel")
        assert p.n_transfers == 0
        assert all(n.unit == 0 for n in p.graph.nodes)
        assert len(p.graph) == len(g)

    def test_layer_gran_epilogue_inserts_reduction_transfers(self):
        g = self._gemm_graph(granularity=Granularity.LAYER,
                             vector_ops={"relu": 256 * 256.0})
        p = partition_graph(g, 4, "row-panel")
        # the single epilogue consumes tiles from 3 remote units
        xfer = [n for n in p.graph.nodes if n.kind == "memory"]
        assert p.n_transfers == len(xfer) > 0
        assert p.transfer_bytes == sum(n.mem_bytes for n in xfer)
        vec = p.graph.vector_nodes()[0]
        dep_kinds = {p.graph.nodes[d].kind for d in vec.deps}
        assert "memory" in dep_kinds          # remote tiles behind transfers

    def test_panel_gran_row_panel_stays_local(self):
        """Each PANEL epilogue's tiles live on one unit: no transfers."""
        g = self._gemm_graph(granularity=Granularity.PANEL,
                             vector_ops={"relu": 256 * 256.0})
        p = partition_graph(g, 4, "row-panel")
        assert p.n_transfers == 0
        for v in p.graph.vector_nodes():
            units = {p.graph.nodes[d].unit for d in v.deps}
            assert units == {v.unit}

    def test_layer_pipeline_crosses_layers_with_transfers(self):
        layers = [LayerTrace(f"l{i}", (MatMulTask(m=64, n=64, k=64),))
                  for i in range(2)]
        g = workload_to_graph(CASE_STUDY, layers)
        p = partition_graph(g, 2, "layer-pipeline")
        assert p.unit_of_label == {"l0/g0": 0, "l1/g0": 1}
        assert p.n_transfers > 0               # activations cross units

    def test_unknown_strategy_rejected(self):
        with pytest.raises(ValueError, match="strategy"):
            partition_graph(self._gemm_graph(), 2, "diagonal")


# ---------------------------------------------------------------------------
# Cluster simulation: scaling, contention, fidelity.
# ---------------------------------------------------------------------------

def weak_scaling_run(n_units, total_bandwidth=None):
    unit = PLATFORM_2TOPS
    g, _ = build_gemm_graph(MatMulTask(m=512 * n_units, n=512, k=8192),
                            unit.m_scp, unit.n_scp)
    p = partition_graph(g, n_units, "row-panel")
    topo = ClusterTopology(n_units=n_units, unit=unit, platform=SHUTTLE,
                           total_bandwidth=total_bandwidth)
    return simulate_cluster(p.graph, topo)


class TestClusterSim:
    def test_weak_scaling_sustains_85pct_aggregate_util(self):
        """The acceptance pin: 4 units, paper GEMM regime, pooled
        bandwidth — ≥85% aggregate matrix-unit utilization with the
        shared-loader contention visible in the timeline."""
        r = weak_scaling_run(4)
        assert r.n_units == 4
        assert r.aggregate_matrix_utilization >= 0.85
        assert all(u >= 0.85 for u in r.unit_utilizations())
        # contention is visible: transfer spans overlap on the shared
        # loader (total demand exceeds union busy time)
        assert r.loader_contention() > 1.5
        # per-unit timelines exist and stay within the makespan
        for i in range(4):
            ivals = r.intervals[f"u{i}/pe_array"]
            assert ivals
            assert all(0 <= s <= e <= r.cycles + 1e-6 for s, e, _ in ivals)

    def test_fixed_bandwidth_pool_saturates_loader(self):
        """Strong bandwidth pressure: holding the pool at one unit's
        channel collapses aggregate utilization ~1/N past the knee."""
        r1 = weak_scaling_run(1, total_bandwidth=PLATFORM_2TOPS.bandwidth)
        r4 = weak_scaling_run(4, total_bandwidth=PLATFORM_2TOPS.bandwidth)
        assert r4.loader_utilization > 0.95          # saturated
        assert r4.aggregate_matrix_utilization < \
            0.5 * r1.aggregate_matrix_utilization
        assert r4.cycles > 2.0 * r1.cycles

    def test_pooled_weak_scaling_holds_makespan(self):
        r1, r4 = weak_scaling_run(1), weak_scaling_run(4)
        assert r4.cycles == pytest.approx(r1.cycles, rel=0.05)

    def test_unit_out_of_range_rejected(self):
        g, _ = build_gemm_graph(MatMulTask(m=128, n=64, k=64), 64, 64)
        p = partition_graph(g, 4, "row-panel")
        topo = ClusterTopology(n_units=2, unit=PLATFORM_2TOPS,
                               platform=SHUTTLE)
        with pytest.raises(ValueError, match="unit"):
            simulate_cluster(p.graph, topo)

    def test_transfers_occupy_shared_loader(self):
        g, _ = build_gemm_graph(MatMulTask(m=256, n=256, k=512), 64, 64,
                                granularity=Granularity.LAYER,
                                vector_ops={"relu": 256 * 256.0})
        p = partition_graph(g, 4, "row-panel")
        topo = ClusterTopology(n_units=4, unit=PLATFORM_2TOPS,
                               platform=SHUTTLE)
        r = simulate_cluster(p.graph, topo)
        xfer_spans = [iv for iv in r.intervals["mem_loader"]
                      if "/xfer@" in iv[2]]
        assert len(xfer_spans) == p.n_transfers > 0


class TestKStreamFidelity:
    """DES-fidelity ROADMAP item: K-chunked scratchpad streaming
    (``k_scp`` granularity) overlaps a single tile's fill with its own
    compute."""

    def _single_tile(self, k_stream):
        g, _ = build_gemm_graph(MatMulTask(m=64, n=64, k=8192), 64, 64)
        topo = ClusterTopology(n_units=1, unit=PLATFORM_2TOPS,
                               platform=SHUTTLE, loader_policy="fcfs",
                               k_stream=k_stream)
        return simulate_cluster(g, topo)

    def test_single_tile_latency_shortens(self):
        off = self._single_tile(False)
        on = self._single_tile(True)
        assert on.cycles < 0.75 * off.cycles
        # with streaming, the tile's first PE busy interval starts long
        # before its load stream completes: fill overlaps compute.
        load_end = max(e for s, e, lbl in on.intervals["mem_loader"]
                       if not lbl.endswith("/wb"))
        pe_start = min(s for s, e, _ in on.intervals["pe_array"])
        assert pe_start < 0.1 * load_end

    def test_chunked_equals_whole_tile_work(self):
        """Chunking changes the schedule, not the totals."""
        off, on = self._single_tile(False), self._single_tile(True)
        assert on.busy("pe_array") == pytest.approx(off.busy("pe_array"))
        assert on.ideal_matrix_cycles == off.ideal_matrix_cycles

    def test_gemm_utilization_improves(self):
        g, _ = build_gemm_graph(MatMulTask(m=512, n=512, k=8192), 64, 64)
        rs = [simulate_cluster(g, ClusterTopology(
            n_units=1, unit=PLATFORM_2TOPS, platform=SHUTTLE,
            loader_policy="fcfs", k_stream=ks)) for ks in (False, True)]
        assert rs[1].matrix_utilization >= rs[0].matrix_utilization
        assert rs[1].matrix_utilization > 0.99


# ---------------------------------------------------------------------------
# Trace export: one Perfetto process per unit.
# ---------------------------------------------------------------------------

class TestClusterTrace:
    def test_cluster_trace_pid_per_unit(self, tmp_path):
        r = weak_scaling_run(2)
        path = dump_chrome_trace(r, str(tmp_path / "c.json"))
        data = json.loads(open(path).read())
        events = data["traceEvents"]
        procs = {e["pid"]: e["args"]["name"] for e in events
                 if e["ph"] == "M" and e["name"] == "process_name"}
        # pid 0 = shared resources, pid i+1 = unit i
        assert set(procs) == {0, 1, 2}
        assert "unit0" in procs[1] and "unit1" in procs[2]
        threads = {(e["pid"], e["args"]["name"]) for e in events
                   if e["ph"] == "M" and e["name"] == "thread_name"}
        for pid in (1, 2):
            assert {(pid, "dispatcher"), (pid, "scratchpad"),
                    (pid, "pe_array"), (pid, "vector_unit")} <= threads
        assert (0, "mem_loader") in threads
        # a unit's X events land on that unit's pid; loader on pid 0
        pids_by_cat = {}
        for e in events:
            if e["ph"] == "X":
                pids_by_cat.setdefault(e["cat"], set()).add(e["pid"])
        assert pids_by_cat["u0/pe_array"] == {1}
        assert pids_by_cat["u1/pe_array"] == {2}
        assert pids_by_cat["mem_loader"] == {0}
        assert data["otherData"]["n_units"] == 2
        assert 0 < data["otherData"]["aggregate_matrix_utilization"] <= 1

    def test_single_unit_trace_shape_unchanged(self):
        r = simulate_graph(build_gemm_graph(
            MatMulTask(m=128, n=128, k=256), 64, 64)[0], CASE_STUDY,
            SHUTTLE)
        data = chrome_trace(r)
        events = data["traceEvents"]
        assert all(e["pid"] == 0 for e in events)
        rows = {e["args"]["name"] for e in events
                if e["ph"] == "M" and e["name"] == "thread_name"}
        assert {"dispatcher", "mem_loader", "scratchpad", "pe_array",
                "vector_unit"} <= rows


# ---------------------------------------------------------------------------
# Registry hygiene (satellite): duplicates raise, errors name the options.
# ---------------------------------------------------------------------------

class TestRegistryHygiene:
    def test_cluster_backends_registered(self):
        assert {"desim-cluster", "sharded"} <= set(backend.available())

    def test_unknown_backend_error_lists_names(self):
        with pytest.raises(KeyError) as ei:
            backend.get("verilator")
        msg = str(ei.value)
        for name in backend.available():
            assert name in msg
        assert "analytic" in msg               # aliases shown too

    def test_duplicate_registration_raises(self):
        from repro.backend.base import Backend

        with pytest.raises(ValueError, match="already registered"):
            @backend.register("jax")
            class Impostor(Backend):           # pragma: no cover
                def _stage(self, *a):
                    raise NotImplementedError

                def run_graph(self, *a):
                    raise NotImplementedError
        # the original class is untouched
        assert backend.get("jax").name == "jax"

    def test_reregistering_same_class_idempotent(self):
        cls = type(backend.get("jax"))
        assert backend.register("jax")(cls) is cls

    def test_override_replaces_and_restores(self):
        orig = type(backend.get("desim"))

        @backend.register("desim", override=True)
        class Stand_in(orig):
            pass

        try:
            assert type(backend.get("desim")) is Stand_in
        finally:
            backend.register("desim", override=True)(orig)
        assert type(backend.get("desim")) is orig

    def test_single_unit_backends_reject_units(self):
        for name in ("jax", "pallas", "desim"):
            with pytest.raises(ValueError, match="single matrix unit"):
                backend.get(name, units=4)
            assert backend.get(name, units=1) is not None
        # analytical joined the cluster-aware set in PR 4: units=N
        # switches it to the contention-aware closed form.
        assert backend.get("analytical", units=4).supports_units


# ---------------------------------------------------------------------------
# The two cluster backends behind the registry.
# ---------------------------------------------------------------------------

class TestShardedParity:
    """Acceptance: the partitioned graph executes int8 bit-exact on the
    sharded backend vs the jax backend."""

    @pytest.mark.parametrize("strategy", ["row-panel", "output-tile",
                                          "layer-pipeline"])
    @pytest.mark.parametrize("units", [2, 4])
    def test_int8_bit_exact(self, strategy, units):
        task = MatMulTask(m=128, n=192, k=256)
        a, b = int8_pair(jax.random.PRNGKey(1), 128, 192, 256)
        ops = backend.MatMulOperands(a=a, b=b)
        jx = backend.get("jax")
        ref = np.asarray(jx.wait(jx.dispatch(task, ops)).output)
        sh = backend.get("sharded", units=units, strategy=strategy)
        out = np.asarray(sh.wait(sh.dispatch(task, ops)).output)
        assert out.dtype == ref.dtype == np.int32
        assert (out == ref).all()

    def test_epilogue_graph_matches_jax_backend(self):
        ep = Epilogue(activation="silu", glu=True, out_dtype=jnp.float32)
        task = MatMulTask(m=128, n=256, k=128)
        a, b = int8_pair(jax.random.PRNGKey(4), 128, 256, 128)
        jx = backend.get("jax", granularity="panel")
        graph = jx.lower(task, epilogue=ep)
        ref = jx.run_graph(graph, backend.MatMulOperands(a=a, b=b)).output
        sh = backend.get("sharded", units=2, granularity="panel")
        out = sh.run_graph(graph, backend.MatMulOperands(a=a, b=b)).output
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)
        direct = cute_matmul(a, b, epilogue=ep, backend="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(direct),
                                   rtol=1e-6, atol=1e-6)

    def test_requires_operands(self):
        with pytest.raises(ValueError):
            backend.get("sharded", units=2).dispatch(
                MatMulTask(m=8, n=8, k=8))

    def test_mismatched_partition_rejected(self):
        g, _ = build_gemm_graph(MatMulTask(m=128, n=64, k=64), 64, 64)
        part = partition_graph(g, 4, "row-panel")
        with pytest.raises(ValueError, match="partitioned for 4"):
            backend.get("sharded", units=2).run_graph(part)

    def test_unbalanced_spans_execute_partition_layout(self):
        """m=128 over 4 units leaves two units idle (2 panels): execution
        walks the partition's own spans — not an even 32-row split — and
        stays bit-exact."""
        from repro.distributed.sharding import shard_map_gemm
        g, _ = build_gemm_graph(MatMulTask(m=128, n=64, k=64), 64, 64)
        part = partition_graph(g, 4, "row-panel")
        spans = part.spans["gemm"]
        assert not part.balanced("gemm") and None in spans
        a, b = int8_pair(jax.random.PRNGKey(3), 128, 64, 64)
        ref = np.asarray(cute_matmul(a, b, backend="xla"))
        out = backend.get("sharded", units=4).run_graph(
            part, backend.MatMulOperands(a=a, b=b)).output
        assert (np.asarray(out) == ref).all()
        # the low-level path honours explicit spans too
        acc = shard_map_gemm(a, b, 4, dim="m", bounds=spans)
        assert (np.asarray(acc) == ref).all()

    def test_even_split_needs_devices_or_explicit_loop(self):
        """More units than devices is an error for ``shard_map_gemm``;
        the per-span loop is a separate call, and nothing falls back
        quietly."""
        from repro.distributed.sharding import shard_map_gemm, sliced_gemm
        n = jax.device_count() + 1
        a, b = int8_pair(jax.random.PRNGKey(4), 8 * n, 64, 64)
        with pytest.raises(ValueError, match="devices"):
            shard_map_gemm(a, b, n, dim="m")
        ref = np.asarray(cute_matmul(a, b, backend="xla"))
        for dim in ("m", "n"):
            acc = sliced_gemm(a, b, n, dim=dim)
            assert (np.asarray(acc) == ref).all()


class TestClusterBackend:
    def test_capability_flags(self):
        eng = backend.get("desim-cluster", units=2)
        assert eng.models_time and eng.executes and eng.supports_units
        assert eng.units == 2

    def test_not_zoo_routable(self):
        with pytest.raises(ValueError):
            backend.set_default_matmul_backend("desim-cluster")

    def test_dispatch_wait_returns_contended_timeline(self):
        eng = backend.get("desim-cluster", units=2)
        r = eng.wait(eng.dispatch(MatMulTask(m=512, n=512, k=4096)))
        assert r.cycles > 0
        assert r.timeline.n_units == 2
        assert {"u0/pe_array", "u1/pe_array",
                "mem_loader"} <= set(r.timeline.intervals)
        assert 0 < r.utilization <= 1.0
        assert r.detail["partition"]["n_units"] == 2

    def test_two_units_roughly_halve_the_makespan(self):
        one = backend.get("desim")
        two = backend.get("desim-cluster", units=2)
        task = MatMulTask(m=512, n=512, k=4096)
        r1 = one.wait(one.dispatch(task))
        r2 = two.wait(two.dispatch(task))
        assert r2.cycles < 0.7 * r1.cycles

    def test_executes_partitioned_graph_bit_exact(self):
        task = MatMulTask(m=128, n=128, k=256)
        a, b = int8_pair(jax.random.PRNGKey(2), 128, 128, 256)
        eng = backend.get("desim-cluster", units=2)
        r = eng.wait(eng.dispatch(task, backend.MatMulOperands(a=a, b=b)))
        ref = np.asarray(cute_matmul(a, b, backend="xla"))
        assert (np.asarray(r.output) == ref).all()
        assert r.cycles > 0                    # both halves of the claim

    def test_run_workload_dict_shape(self):
        layers = [LayerTrace("l", (MatMulTask(m=128, n=256, k=512),),
                             vector_ops={"silu": 128 * 256.0}, repeat=2)]
        r = backend.get("desim-cluster", units=2).run_workload(layers)
        assert {"cycles", "matrix", "vector", "seconds", "flops",
                "matrix_utilization", "loader_utilization"} <= set(r)
        single = backend.get("desim").run_workload(layers)
        assert r["cycles"] < single["cycles"]

    def test_strategy_validated(self):
        with pytest.raises(ValueError, match="strategy"):
            backend.get("desim-cluster", units=2, strategy="diagonal")


# ---------------------------------------------------------------------------
# Serving schedules priced on the contended cluster.
# ---------------------------------------------------------------------------

class TestServingOnCluster:
    @pytest.fixture(scope="class")
    def engine(self):
        from repro.configs.registry import get_config
        from repro.serving.engine import ServingEngine
        cfg = get_config("yi-6b", reduced=True)
        eng = ServingEngine(cfg, params=None, max_batch=2, cache_len=64)
        key = jax.random.PRNGKey(0)
        for i in range(3):
            key, sub = jax.random.split(key)
            eng.submit(jax.random.randint(sub, (4 + i,), 0, 100))
        return eng

    def test_plan_records_units(self, engine):
        sched = engine.plan(max_new_tokens=4, units=4)
        assert sched.units == 4
        assert engine.plan(max_new_tokens=4).units == 1

    def test_evaluate_schedule_on_cluster(self, engine):
        # output-tile: serving GEMMs are short (few token rows) but wide
        # (hidden dim) — sharding N is what actually spreads the work.
        sched, res = engine.evaluate_schedule(
            "desim-cluster", max_new_tokens=4, units=2,
            strategy="output-tile")
        assert sched.units == 2
        assert res.timeline.n_units == 2
        assert {"u0/pe_array", "u1/pe_array"} <= set(res.timeline.intervals)
        # both units genuinely compute
        assert all(u > 0 for u in res.timeline.unit_utilizations())
        assert res.detail["workload"]["cycles"] >= res.cycles
        single, r1 = engine.evaluate_schedule("desim", max_new_tokens=4)
        assert res.detail["workload"]["cycles"] < \
            r1.detail["workload"]["cycles"]

    def test_sharded_executes_schedule_bit_exact(self, engine):
        sched = engine.plan(max_new_tokens=4, units=2)
        ops = sched.example_operands(jax.random.PRNGKey(7))
        jx = backend.get("jax")
        rj = jx.run_graph(jx.lower(sched.layers), ops)
        sh = backend.get("sharded", units=2)
        rs = sh.run_graph(sh.lower(sched.layers), ops)
        assert set(rs.outputs) == set(rj.outputs) == set(ops)
        for label in ops:
            assert (np.asarray(rs.outputs[label])
                    == np.asarray(rj.outputs[label])).all(), label


# ---------------------------------------------------------------------------
# Shared DRAM row-buffer state across units' interleaved streams.
# ---------------------------------------------------------------------------

class TestRowBufferInterleaving:
    """``ClusterTopology.row_buffer``: N shared-pool streams chop each
    other's contiguous runs (``dram_stride_efficiency``'s ``streams``
    knob).  Opt-in — the default is bit-identical to the calibrated flat
    derate — and the DES and the analytical closed form stay within 5%
    of each other with it enabled."""

    # narrow tiles cut from a wide row-major matrix on a small fixed
    # pool: short runs + loader-bound, where interleaving actually bites.
    TASK = MatMulTask(m=512, n=128, k=2048, stride_b=8192, stride_c=8192)

    def _pair(self, n, row_buffer):
        from repro.core.hardware import GIGA
        unit = PLATFORM_2TOPS
        g, _ = build_gemm_graph(self.TASK, unit.m_scp, unit.n_scp)
        part = partition_graph(g, n, "row-panel")
        topo = ClusterTopology(n_units=n, unit=unit, platform=SHUTTLE,
                               total_bandwidth=16 * GIGA,
                               row_buffer=row_buffer)
        des = simulate_cluster(part.graph, topo)
        ana = backend.get("analytical", topology=topo).run_graph(part)
        return des, ana

    def test_streams_chop_runs(self):
        from repro.sim.resources import dram_stride_efficiency
        base = SHUTTLE.dram_efficiency
        # default reproduces the single-stream curve exactly
        assert dram_stride_efficiency(256.0, base, streams=1) == \
            pytest.approx(dram_stride_efficiency(256.0, base))
        # more interleaved streams -> shorter effective runs; long runs
        # only degrade once chopped below the 64-byte reference burst
        assert dram_stride_efficiency(256.0, base, 4) == \
            pytest.approx(base)                       # 64 B each: still ok
        e1 = dram_stride_efficiency(96.0, base)
        e2 = dram_stride_efficiency(96.0, base, 2)
        e4 = dram_stride_efficiency(96.0, base, 4)
        assert e4 < e2 < e1 == pytest.approx(base)
        # N streams of run R behave like one stream of run R/N
        assert dram_stride_efficiency(128.0, base, 2) == \
            pytest.approx(dram_stride_efficiency(64.0, base))

    def test_topology_stream_count(self):
        from repro.core.hardware import GIGA
        from repro.sim import UnitSpec
        topo = ClusterTopology(n_units=4, unit=PLATFORM_2TOPS)
        assert topo.interleaved_streams() == 1       # off by default
        assert topo.with_(row_buffer=True).interleaved_streams() == 4
        # private slices never interleave on the shared pool
        het = ClusterTopology(
            unit_specs=(UnitSpec(unit=PLATFORM_2TOPS,
                                 private_bandwidth=24 * GIGA),
                        UnitSpec(unit=PLATFORM_2TOPS),
                        UnitSpec(unit=PLATFORM_2TOPS)),
            total_bandwidth=96 * GIGA, row_buffer=True)
        assert het.interleaved_streams() == 2

    def test_default_off_is_bit_identical(self):
        """row_buffer=False (the default) must not move a single cycle —
        the existing calibration pins stay valid."""
        unit = PLATFORM_2TOPS
        g, _ = build_gemm_graph(self.TASK, unit.m_scp, unit.n_scp)
        part = partition_graph(g, 2, "row-panel")
        base = ClusterTopology(n_units=2, unit=unit, platform=SHUTTLE)
        expl = base.with_(row_buffer=False)
        assert simulate_cluster(part.graph, base).cycles == \
            simulate_cluster(part.graph, expl).cycles
        # ... and a single unit never interleaves with itself
        solo = ClusterTopology(n_units=1, unit=unit, platform=SHUTTLE)
        assert simulate_cluster(g, solo.with_(row_buffer=True)).cycles \
            == simulate_cluster(g, solo).cycles

    @pytest.mark.parametrize("n", [2, 4])
    def test_interleaving_costs_visible_makespan(self, n):
        des_off, _ = self._pair(n, row_buffer=False)
        des_on, _ = self._pair(n, row_buffer=True)
        # more streams -> worse locality -> monotonically costlier
        floor = {2: 1.05, 4: 1.2}[n]
        assert des_on.cycles > floor * des_off.cycles

    @pytest.mark.parametrize("n", [2, 4])
    def test_des_vs_analytical_within_5pct(self, n):
        des, ana = self._pair(n, row_buffer=True)
        assert abs(ana.cycles / des.cycles - 1.0) <= 0.05
