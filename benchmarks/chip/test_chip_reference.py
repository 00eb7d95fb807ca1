"""The float32 reference against the program's ``generate``, and the
float8 control against both, at the registry's ``reduced()`` sizes."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import traffic  # noqa: E402

TINY = {
    "yi-6b": dict(hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2,
                  rms_norm_eps=1e-5, rope_theta=5e6),
    "deepseek-67b": dict(hidden_size=96, intermediate_size=192,
                         num_attention_heads=6, num_key_value_heads=2,
                         rms_norm_eps=1e-6, rope_theta=1e4),
}


def tiny(registry: str) -> dict:
    """A configuration file at the widths of ``get_config(registry,
    reduced=True)``."""
    return {"name": f"{registry}-tiny", "family": "transformer",
            "registry": registry, "num_hidden_layers": 3, "vocab_size": 512,
            "hidden_act": "silu", "attention_bias": False,
            "tie_word_embeddings": False, "torch_dtype": "bfloat16",
            **TINY[registry]}


def tiny_cfg(conf):
    from repro.configs.registry import get_config
    return harness.program_config(
        conf, base=get_config(conf["registry"], reduced=True))


def serve(conf, seed, batch=4, prompt=48, new=16):
    """The program's greedy ``generate`` on the benchmark's weights."""
    import jax.numpy as jnp
    from repro.serving.engine import generate
    ref = harness.reference_module(conf)
    key = harness.seed_key(seed)
    prompts = traffic.prompts(seed, 0, batch, prompt, conf["vocab_size"])
    res = generate(tiny_cfg(conf), ref.program_params(conf, key),
                   {"tokens": jnp.asarray(prompts)}, max_new_tokens=new,
                   cache_len=prompt + new, keep_logits=True)
    return ref, key, prompts, np.asarray(res.tokens), np.asarray(res.logits)


@pytest.mark.parametrize("registry", sorted(TINY))
def test_reference_agrees_with_generate(registry):
    conf = tiny(registry)
    ref, key, prompts, served, logits = serve(conf, seed=2 ** 31 + 5)
    seq = np.concatenate([prompts, served[:, :-1]], axis=1)
    want = np.asarray(ref.logits(conf, key, seq, prompts.shape[1] - 1))
    # The logits have a spread of about 1; bfloat16 weights and
    # activations move them by a few hundredths.
    assert want.std() == pytest.approx(1.0, abs=0.2)
    assert np.abs(want - logits).max() < 0.08
    gaps = ref.served_gaps(conf, key, prompts, served)
    assert gaps.shape == served.shape and gaps.min() >= 0
    assert gaps.max() < 0.02


def test_program_params_are_the_layerwise_draws():
    import jax
    conf = tiny("yi-6b")
    ref = harness.reference_module(conf)
    key = harness.seed_key(11)
    params = ref.program_params(conf, key)
    t = ref.dims(conf)
    layer = params["layers"][0]
    for i in range(t.layers):
        w = ref.layer_weights(t, key, i)
        np.testing.assert_array_equal(layer["attn"]["wq"][i], w["wq"])
        np.testing.assert_array_equal(layer["mlp"]["wi"][i], w["wi"])
        np.testing.assert_array_equal(layer["mlp"]["wo"][i], w["wd"])
        np.testing.assert_array_equal(layer["ln_mlp"][i], w["ln_mlp"])
    top = ref.top_weights(t, key)
    np.testing.assert_array_equal(params["lm_head"], top["head"])
    assert all(a.dtype == jax.numpy.bfloat16
               for a in jax.tree.leaves(params))


def _limits(config):
    """The greedy-gap limits of the cells that serve ``config``."""
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]
                 if w["config"] == config]
    out = []
    for name in cells:
        with open(os.path.join(HERE, "limits", name + ".json")) as f:
            out.append(json.load(f)["greedy_gap"])
    return out


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_is_not_correct(seed):
    """The control (the reference with every matmul operand in float8)
    reads a greedy gap above the limit of every yi-6b cell, where the
    program reads one below it.  (deepseek-67b-6l's control reads 2.96 or
    more on the chip against a limit of 0.3; at these widths it reads
    less, so it is not checked here.)"""
    conf = tiny("yi-6b")
    ref, key, prompts, served, _ = serve(conf, seed, batch=8, prompt=64,
                                         new=32)
    limits = _limits("yi-6b")
    assert limits
    gap = ref.served_gaps(conf, key, prompts, served).max()
    control = ref.control_gaps(conf, key, prompts, served).max()
    assert gap <= min(limits)
    assert control > max(limits)
