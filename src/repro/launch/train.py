"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \\
        --steps 200 --global-batch 8 --seq-len 128 --ckpt-dir /tmp/run1

Production shape: config → mesh → sharded state → fault-tolerant loop
(async checkpoints, straggler watchdog, preemption handler, auto-resume).
``--mesh host`` spans the first ``--devices`` devices (all by default)
as a (data, model) mesh with ``--model-parallel`` on the model axis; on
a real cluster ``single``/``multi`` drive the 16×16 / 2×16×16 meshes.
``--layers`` cuts the model's depth, keeping its widths.  The state is
created already sharded (one jitted program each for the parameters and
the optimizer), so no device ever holds the whole of it.
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import ALL_ARCHS, get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed import logical, sharding
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models.base import family_module, init_params
from repro.optim import adamw
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.watchdog import PreemptionHandler, StepWatchdog
from repro.training.train_step import TrainConfig, make_train_step


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, default="yi-6b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=("host", "single", "multi"),
                    default="host")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--devices", type=int, default=None,
                    help="--mesh host over the first N devices (all)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model to N layers (published widths)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def init_state(cfg, tcfg: TrainConfig, mesh, key):
    """(params, opt_state), each created by one jitted program directly
    in the shardings the name rules give it on ``mesh``."""
    mod = family_module(cfg)
    abstract = jax.eval_shape(functools.partial(mod.init, cfg), key)
    pshard = sharding.param_shardings(abstract, mesh)
    params = init_params(cfg, key, out_shardings=pshard)
    opt_init = functools.partial(adamw.init, tcfg.optimizer)
    oshard = sharding.param_shardings(jax.eval_shape(opt_init, params),
                                      mesh)
    return params, jax.jit(opt_init, out_shardings=oshard)(params)


def device_memory() -> "list[dict]":
    """Per-device bytes in use and peak, where the backend reports them."""
    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append({"device": d.id,
                    "bytes_in_use": st.get("bytes_in_use"),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return out


def main(argv=None):
    """Train; returns ``{"loss": [...], "grad_norm": [...], "memory":
    device_memory()}`` with one entry per step run (memory is read while
    the state is still alive)."""
    args = parse_args(argv)
    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    if args.reduced:
        cfg = cfg.with_(dtype=jnp.float32, remat="none")
    if args.layers is not None:
        cfg = cfg.with_(n_layers=args.layers)

    if args.mesh == "host":
        devices = jax.devices()[:args.devices]
        model = min(args.model_parallel, len(devices))
        mesh = make_mesh((len(devices) // model, model), ("data", "model"),
                         devices=devices)
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))

    tcfg = TrainConfig(
        optimizer=adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                                    warmup_steps=max(args.steps // 20, 1)),
        microbatches=args.microbatches,
        grad_compression=args.grad_compression,
        loss_chunk=min(512, args.seq_len))
    step_fn = make_train_step(cfg, tcfg)

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  global_batch=args.global_batch,
                                  seq_len=args.seq_len, seed=args.seed))

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    watchdog = StepWatchdog()
    preempt = PreemptionHandler()
    history = {"loss": [], "grad_norm": []}

    with logical.use_rules(mesh, None):
        params, opt = init_state(cfg, tcfg, mesh,
                                 jax.random.PRNGKey(args.seed))
        residual = None
        start = 0
        if mgr and mgr.latest_step() is not None:
            restored, extra = mgr.restore(mgr.latest_step(),
                                          {"params": params, "opt": opt})
            params, opt = restored["params"], restored["opt"]
            data.load_state_dict(extra["data"])
            start = extra["train_step"]
            print(f"resumed from step {start}")

        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
        for step in range(start, args.steps):
            t0 = time.perf_counter()
            batch = next(data)
            params, opt, metrics, residual = jit_step(params, opt, batch,
                                                      residual)
            loss = float(metrics["loss"])
            gnorm = float(metrics["grad_norm"])
            dt = time.perf_counter() - t0
            history["loss"].append(loss)
            history["grad_norm"].append(gnorm)
            slow = watchdog.record_step(dt)
            if step % args.log_every == 0 or slow:
                tag = " STRAGGLER" if slow else ""
                print(f"step {step:5d} loss {loss:.4f} gnorm {gnorm:.3f} "
                      f"{dt * 1e3:.0f}ms{tag}", flush=True)
            want_ckpt = mgr and ((step + 1) % args.ckpt_every == 0
                                 or preempt.requested)
            if want_ckpt:
                mgr.save_async(step + 1, {"params": params, "opt": opt},
                               extra={"data": data.state_dict(),
                                      "train_step": step + 1})
            if preempt.requested:
                print("preemption requested: checkpointed, exiting")
                break
        if mgr:
            mgr.wait()
        history["memory"] = device_memory()
    watchdog.close()
    print(f"done: {watchdog.steps} steps, "
          f"{watchdog.straggler_events} straggler events")
    return history


if __name__ == "__main__":
    main()
