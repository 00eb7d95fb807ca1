"""Smoke test of the device path on a TPU.

    python chip_smoke.py             # one chip: serve yi-6b
    python chip_smoke.py --chips 4   # four chips: sharded yi-6b training

One chip: builds yi-6b at its published widths (32 layers, d_model 4096,
32 query / 4 KV heads, d_ff 11008, vocab 64000, bf16) with random
weights drawn from ``--seed``, serves 8 requests of 512 prompt tokens
through ``ServingEngine.submit``/``run``, checks the greedy decode
against a teacher-forced ``forward`` over the generated sequence, and
runs one batch through the Pallas kernels (``cfg.backend="pallas"`` and
the Pallas matmul route), which must be compiled kernels, must agree
with the XLA route and must pass the same teacher-forced check.

Four chips: three ``launch.train`` steps of yi-6b at published widths
cut to 2 layers on one chip and on a (1, 4) (data, model) mesh, whose
losses and first gradient norm must agree; then three steps of the 12-layer
cut on the mesh, whose state must be spread over the four chips.

Lines before the last are smoke observations, not benchmark metrics.
The last line is ``{"ok": true, "device": {...}}``.  Any failed check or
phase ends the run with a non-zero exit code and no such line; so does a
host without a TPU, or a directory without the program (``src/repro``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Limits of the serving checks, as shares of the largest logit.  Each
# sits between the sound readings on a TPU v5e over seeds 0-2 and the
# readings with a planted decode fault (the KV cache written one slot
# early; decode attention missing the new token's own slot): see PERF.md.
# Largest difference between two bf16 routes through the model: sound
# 0.77-1.65%, faults 2.03-9.17%.
BF16_LOGIT_TOL = 0.018
# How far below the forward's best logit a greedy token may be: sound
# 0.04-0.40%, faults 0.73-1.23%.
GREEDY_GAP_TOL = 0.0055
# Relative bound on the losses and the first gradient norm of the same
# training steps on one chip and on four: sound readings reach 1.6e-4.
# Later gradient norms are not compared: the first Adam step moves every
# weight by about lr * sign(g), so bf16 rounding in a near-zero gradient
# component flips a whole step of that weight.
BF16_TRAIN_RTOL = 1e-3

PROMPT_LEN, NEW_TOKENS, REQUESTS, BATCH = 512, 32, 8, 4


def observe(**kw):
    print("smoke observation: " + json.dumps(kw), flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def serve_phase(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import backend
    from repro.configs.registry import get_config
    from repro.models.base import family_module, init_params
    from repro.serving.engine import (ServingEngine, generate,
                                      lower_generate)

    cfg = get_config("yi-6b")
    check((cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab_size, cfg.dtype)
          == (32, 4096, 32, 4, 11008, 64000, jnp.bfloat16),
          "yi-6b is not at its published widths")
    mod = family_module(cfg)
    cache_len = PROMPT_LEN + NEW_TOKENS
    dev = jax.devices()[0]

    t = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, jax.random.PRNGKey(seed)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    observe(phase="init", params=n_params,
            seconds=time.perf_counter() - t)

    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (REQUESTS, PROMPT_LEN), 0, cfg.vocab_size)
    batch0 = {"tokens": prompts[:BATCH]}
    gen = dict(max_new_tokens=NEW_TOKENS, cache_len=cache_len)

    t = time.perf_counter()
    lower_generate(cfg, params, batch0, **gen).compile()
    observe(phase="compile generate (xla)",
            seconds=time.perf_counter() - t)

    eng = ServingEngine(cfg, params, max_batch=BATCH, cache_len=cache_len)
    for p in prompts:
        eng.submit(p)
    t = time.perf_counter()
    outs = jax.block_until_ready(eng.run(max_new_tokens=NEW_TOKENS))
    serve_s = time.perf_counter() - t
    tokens = np.stack([np.asarray(o) for o in outs])
    check(tokens.shape == (REQUESTS, NEW_TOKENS),
          f"served tokens have shape {tokens.shape}")
    check(((tokens >= 0) & (tokens < cfg.vocab_size)).all(),
          "a served token is outside the vocabulary")
    observe(phase="serve", requests=REQUESTS, prompt_len=PROMPT_LEN,
            new_tokens=NEW_TOKENS, seconds=serve_s,
            seconds_per_decode_step=serve_s / (REQUESTS // BATCH
                                               * NEW_TOKENS))

    # Greedy decode against a teacher-forced forward over its own output.
    forward = jax.jit(mod.forward, static_argnums=0)

    def teacher_forced(res, route):
        full = jnp.concatenate([batch0["tokens"], res.tokens[:, :-1]], axis=1)
        t = time.perf_counter()
        tf = forward(cfg, params, {"tokens": full})[:, PROMPT_LEN - 1:]
        check(bool(jnp.all(jnp.isfinite(tf))), "non-finite forward logits")
        scale = float(jnp.abs(tf).max())
        diff = float(jnp.abs(tf - res.logits).max())
        picked = jnp.take_along_axis(tf, res.tokens[..., None], axis=-1)
        gap = float((tf.max(axis=-1) - picked[..., 0]).max())
        observe(phase=f"teacher-forced check ({route})",
                max_logit_diff=diff, logit_scale=scale, greedy_gap=gap,
                seconds=time.perf_counter() - t)
        check(diff <= BF16_LOGIT_TOL * scale,
              f"{route} decode logits differ from forward by {diff} "
              f"(scale {scale})")
        check(gap <= GREEDY_GAP_TOL * scale,
              f"a {route} greedy token is {gap} below the forward's best "
              f"logit (scale {scale})")

    res = generate(cfg, params, batch0, keep_logits=True, **gen)
    check(bool(jnp.all(jnp.isfinite(res.logits))), "non-finite logits")
    check((np.asarray(res.tokens) == tokens[:BATCH]).all(),
          "generate and ServingEngine.run disagree on the same batch")
    teacher_forced(res, "xla")

    # The same batch through the Pallas kernels.
    pcfg = cfg.with_(backend="pallas")
    prev = backend.set_default_matmul_backend("pallas")
    try:
        t = time.perf_counter()
        lowered = lower_generate(pcfg, params, batch0, keep_logits=True,
                                 **gen)
        check("tpu_custom_call" in lowered.as_text(),
              "the Pallas route lowered without a compiled TPU kernel")
        lowered.compile()
        observe(phase="compile generate (pallas)",
                seconds=time.perf_counter() - t)
        pres = generate(pcfg, params, batch0, keep_logits=True, **gen)
    finally:
        backend.set_default_matmul_backend(prev)
    check(bool(jnp.all(jnp.isfinite(pres.logits))),
          "non-finite Pallas logits")
    # Logits are comparable up to and including the first step at which
    # the two routes pick different tokens.
    same = np.cumprod(np.asarray(pres.tokens) == np.asarray(res.tokens),
                      axis=1)
    upto = np.minimum(same.sum(axis=1) + 1, NEW_TOKENS)
    mask = np.arange(NEW_TOKENS)[None, :] < upto[:, None]
    d = np.abs(np.asarray(pres.logits) - np.asarray(res.logits)).max(-1)
    scale = float(jnp.abs(res.logits).max())
    diff = float(d[mask].max())
    observe(phase="pallas check", max_logit_diff=diff, logit_scale=scale,
            steps_compared=int(mask.sum()),
            tokens_agree=float((np.asarray(pres.tokens)
                                == np.asarray(res.tokens)).mean()))
    check(diff <= BF16_LOGIT_TOL * scale,
          f"Pallas logits differ from XLA by {diff} (scale {scale})")
    teacher_forced(pres, "pallas")

    observe(phase="memory",
            peak_bytes_in_use=dev.memory_stats().get("peak_bytes_in_use"),
            bytes_limit=dev.memory_stats().get("bytes_limit"))


def train_phase(seed: int):
    import math
    from repro.launch import train

    common = ["--arch", "yi-6b", "--steps", "3", "--global-batch", "4",
              "--seq-len", "512", "--log-every", "1", "--seed", str(seed)]
    runs = {}
    for name, extra in (("2 layers, 1 chip", ["--devices", "1"]),
                        ("2 layers, 4 chips", ["--model-parallel", "4"])):
        t = time.perf_counter()
        runs[name] = train.main(common + ["--layers", "2"] + extra)
        observe(phase=f"train {name}", loss=runs[name]["loss"],
                grad_norm=runs[name]["grad_norm"],
                seconds=time.perf_counter() - t)
    a, b = runs.values()
    for x, y in zip(a["loss"] + a["grad_norm"][:1],
                    b["loss"] + b["grad_norm"][:1]):
        check(abs(x - y) <= BF16_TRAIN_RTOL * abs(x),
              f"one chip ({a['loss']}, first grad norm {a['grad_norm'][0]})"
              f" and four ({b['loss']}, {b['grad_norm'][0]}) differ")

    t = time.perf_counter()
    deep = train.main(common + ["--layers", "12", "--model-parallel", "4"])
    observe(phase="train 12 layers, 4 chips", loss=deep["loss"],
            grad_norm=deep["grad_norm"], memory=deep["memory"],
            seconds=time.perf_counter() - t)
    check(all(math.isfinite(x) for x in deep["loss"] + deep["grad_norm"]),
          "non-finite loss or gradient norm")
    used = [m["bytes_in_use"] for m in deep["memory"]]
    check(None not in used and max(used) < 0.5 * sum(used),
          f"the training state is not spread over the chips: {used}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the program is not here ({e}); run from the "
              "root of a checkout", file=sys.stderr)
        return 2
    import jax

    cache = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found; JAX sees {devices[0].platform} "
              "devices only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, found {len(devices)}", file=sys.stderr)
        return 1
    observe(compile_cache=cache, device_kind=devices[0].device_kind,
            devices=len(devices))

    t = time.perf_counter()
    if args.chips == 1:
        serve_phase(args.seed)
    else:
        train_phase(args.seed)
    observe(phase="total", seconds=time.perf_counter() - t)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
