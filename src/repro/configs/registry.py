"""Architecture registry + the assigned shape grid + input_specs().

``input_specs(cfg, shape, mode)`` returns ShapeDtypeStruct stand-ins for
every model input — weak-type-correct, shardable, no device allocation —
consumed by compile-only rehearsals (``jax.jit(...).lower(...)``).
"""

from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp

from repro.models.base import ArchConfig

ARCH_MODULES = {
    "gemma2-2b": "repro.configs.gemma2_2b",
    "gemma2-27b": "repro.configs.gemma2_27b",
    "deepseek-67b": "repro.configs.deepseek_67b",
    "yi-6b": "repro.configs.yi_6b",
    "internvl2-1b": "repro.configs.internvl2_1b",
    "rwkv6-7b": "repro.configs.rwkv6_7b",
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    "arctic-480b": "repro.configs.arctic_480b",
    "whisper-tiny": "repro.configs.whisper_tiny",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
}

ALL_ARCHS = tuple(ARCH_MODULES)


def get_config(name: str, reduced: bool = False, **overrides) -> ArchConfig:
    mod = importlib.import_module(ARCH_MODULES[name])
    cfg = mod.reduced() if reduced else mod.CONFIG
    return cfg.with_(**overrides) if overrides else cfg


# ---------------------------------------------------------------------------
# Assigned shapes (LM shapes are seq_len × global_batch).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    mode: str                 # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

#: bounded-state archs that run the long-context decode cell.
LONG_CONTEXT_ARCHS = ("rwkv6-7b", "recurrentgemma-2b")


def cell_applicable(arch: str, shape: str) -> bool:
    """Assignment rule: long_500k only for bounded-state archs."""
    if shape == "long_500k":
        return arch in LONG_CONTEXT_ARCHS
    return True


def all_cells(include_skipped: bool = False):
    for arch in ALL_ARCHS:
        for shape in SHAPES:
            if include_skipped or cell_applicable(arch, shape):
                yield arch, shape


# ---------------------------------------------------------------------------
# Abstract inputs.
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ArchConfig, shape: "ShapeSpec | str",
                mode: "str | None" = None) -> dict:
    """Abstract batch for one (arch × shape) cell.

    train:   tokens + labels (B, S)         [+ stub frontend tensors]
    prefill: tokens (B, S)                  [+ stub frontend tensors]
    decode:  tokens (B, 1)                  (cache is built separately)
    """
    if isinstance(shape, str):
        shape = SHAPES[shape]
    mode = mode or shape.mode
    b, s = shape.global_batch, shape.seq_len
    specs = {}
    if mode == "decode":
        specs["tokens"] = _sds((b, 1), jnp.int32)
    else:
        specs["tokens"] = _sds((b, s), jnp.int32)
        if mode == "train":
            specs["labels"] = _sds((b, s), jnp.int32)
    if cfg.vision_prefix and mode != "decode":
        specs["vision_embeds"] = _sds((b, cfg.vision_prefix, cfg.d_model),
                                      jnp.float32)
    if cfg.encdec is not None and mode != "decode":
        specs["audio_embeds"] = _sds((b, cfg.encdec.n_audio_ctx, cfg.d_model),
                                     jnp.float32)
    return specs


def concrete_batch(cfg: ArchConfig, batch_size: int, seq_len: int,
                   mode: str, key=None) -> dict:
    """Small concrete batch for smoke tests (mirrors input_specs)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    spec = ShapeSpec("smoke", seq_len, batch_size, mode)
    out = {}
    for name, s in input_specs(cfg, spec, mode).items():
        if s.dtype == jnp.int32:
            out[name] = jax.random.randint(ks[0], s.shape, 0,
                                           cfg.vocab_size, jnp.int32)
        else:
            out[name] = jax.random.normal(ks[1], s.shape, s.dtype)
    return out
