"""End-to-end system behaviour: fault-tolerant training (crash/resume
equivalence)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.base import family_module
from repro.optim import adamw
from repro.runtime.checkpoint import CheckpointManager
from repro.training.train_step import TrainConfig, make_train_step


def _tiny():
    cfg = get_config("yi-6b", reduced=True).with_(
        remat="none", dtype=jnp.float32, n_layers=2, d_model=32, d_ff=64,
        n_heads=2, n_kv_heads=2, head_dim=16, vocab_size=64, attn_chunk=16)
    return cfg, family_module(cfg)


def test_crash_resume_is_bit_identical(tmp_path):
    """Train 6 steps straight vs 3 steps -> checkpoint -> 'crash' ->
    restore -> 3 steps: identical parameters and data stream."""
    cfg, mod = _tiny()
    tcfg = TrainConfig(loss_chunk=16,
                       optimizer=adamw.AdamWConfig(lr=1e-3, warmup_steps=0))
    step = jax.jit(make_train_step(cfg, tcfg))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, global_batch=4, seq_len=16)

    # --- uninterrupted run ------------------------------------------------
    params = mod.init(cfg, jax.random.PRNGKey(0))
    opt = adamw.init(tcfg.optimizer, params)
    data = SyntheticLM(dcfg)
    for _ in range(6):
        params, opt, _, _ = step(params, opt, next(data))
    straight = params

    # --- crash at step 3, resume -------------------------------------------
    mgr = CheckpointManager(str(tmp_path))
    params = mod.init(cfg, jax.random.PRNGKey(0))
    opt = adamw.init(tcfg.optimizer, params)
    data = SyntheticLM(dcfg)
    for _ in range(3):
        params, opt, _, _ = step(params, opt, next(data))
    mgr.save(3, {"params": params, "opt": opt},
             extra={"data": data.state_dict()})
    del params, opt, data                      # "crash"

    p0 = mod.init(cfg, jax.random.PRNGKey(0))
    o0 = adamw.init(tcfg.optimizer, p0)
    restored, extra = mgr.restore(mgr.latest_step(),
                                  {"params": p0, "opt": o0})
    params, opt = restored["params"], restored["opt"]
    data = SyntheticLM(dcfg)
    data.load_state_dict(extra["data"])
    for _ in range(3):
        params, opt, _, _ = step(params, opt, next(data))

    for a, b in zip(jax.tree.leaves(straight), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
