"""Fault-tolerance substrate: checkpointing, watchdog, data pipeline."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import DataConfig, SyntheticLM
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.watchdog import PreemptionHandler, StepWatchdog


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        tree = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((3, 4))}}
        mgr.save(5, tree, extra={"data_step": 17})
        restored, extra = mgr.restore(5, tree)
        assert extra == {"data_step": 17}
        for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_async_save_and_latest(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        tree = {"w": jnp.ones(16)}
        mgr.save_async(1, tree)
        mgr.save_async(2, tree)
        mgr.wait()
        assert mgr.latest_step() == 2

    def test_keep_n_gc(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for s in range(5):
            mgr.save(s, {"w": jnp.ones(4)})
        assert mgr.all_steps() == [3, 4]

    def test_atomic_no_tmp_left(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"w": jnp.ones(4)})
        assert not any(d.endswith("_tmp") for d in os.listdir(tmp_path))

    def test_structure_mismatch_detected(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"w": jnp.ones(4)})
        with pytest.raises(ValueError):
            mgr.restore(1, {"w": jnp.ones(4), "extra": jnp.ones(2)})

    def test_elastic_restore_with_new_sharding(self, tmp_path):
        """Checkpoints are mesh-agnostic: restore with fresh shardings."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mgr = CheckpointManager(str(tmp_path))
        tree = {"w": jnp.arange(16.0).reshape(4, 4)}
        mgr.save(1, tree)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
        sh = {"w": NamedSharding(mesh, P("data", None))}
        restored, _ = mgr.restore(1, tree, shardings=sh)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        assert restored["w"].sharding == sh["w"]


class TestWatchdog:
    def test_straggler_detection(self):
        wd = StepWatchdog(ema_alpha=0.5, threshold=2.0)
        for _ in range(5):
            assert not wd.record_step(1.0)
        assert wd.record_step(5.0)           # 5x the EMA
        assert wd.straggler_events == 1

    def test_ema_outlier_clamped(self):
        wd = StepWatchdog(ema_alpha=0.5, threshold=2.0)
        wd.record_step(1.0)
        wd.record_step(100.0)                # clamped into the EMA
        assert wd.ema < 5.0

    def test_hang_callback(self):
        fired = []
        wd = StepWatchdog(hang_timeout=0.2, on_hang=lambda: fired.append(1))
        time.sleep(0.5)
        wd.close()
        assert fired

    def test_preemption_flag(self):
        import signal
        h = PreemptionHandler(signals=(signal.SIGUSR1,))
        assert not h.requested
        os.kill(os.getpid(), signal.SIGUSR1)
        time.sleep(0.05)
        assert h.requested
        h.restore()


class TestDataPipeline:
    def test_deterministic_replay(self):
        cfg = DataConfig(vocab_size=128, global_batch=4, seq_len=16)
        a, b = SyntheticLM(cfg), SyntheticLM(cfg)
        for _ in range(3):
            ba, bb = next(a), next(b)
            np.testing.assert_array_equal(np.asarray(ba["tokens"]),
                                          np.asarray(bb["tokens"]))

    def test_state_resume(self):
        cfg = DataConfig(vocab_size=128, global_batch=4, seq_len=16)
        a = SyntheticLM(cfg)
        next(a)
        next(a)
        state = a.state_dict()
        expected = next(a)
        b = SyntheticLM(cfg)
        b.load_state_dict(state)
        got = next(b)
        np.testing.assert_array_equal(np.asarray(expected["tokens"]),
                                      np.asarray(got["tokens"]))

    def test_labels_shift(self):
        cfg = DataConfig(vocab_size=128, global_batch=2, seq_len=16)
        batch = next(SyntheticLM(cfg))
        np.testing.assert_array_equal(np.asarray(batch["tokens"][:, 1:]),
                                      np.asarray(batch["labels"][:, :-1]))

    def test_host_sharding_disjoint(self):
        c0 = DataConfig(vocab_size=128, global_batch=8, seq_len=8,
                        n_hosts=2, host_id=0)
        c1 = DataConfig(vocab_size=128, global_batch=8, seq_len=8,
                        n_hosts=2, host_id=1)
        b0, b1 = next(SyntheticLM(c0)), next(SyntheticLM(c1))
        assert b0["tokens"].shape == (4, 8)
        assert not np.array_equal(np.asarray(b0["tokens"]),
                                  np.asarray(b1["tokens"]))
