"""The two seams between the program and the platform it runs on: the
Pallas ``interpret`` flag, and where the compilation cache lives."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import repro.kernels as kernels
from repro.kernels import resolve_interpret
from repro.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestInterpretDefault:
    def test_cpu_backend_interprets(self):
        assert jax.default_backend() == "cpu"
        assert resolve_interpret() is True
        assert resolve_interpret(None) is True

    def test_explicit_false_compiles(self):
        # how a CPU host compiles kernels for a described TPU
        assert resolve_interpret(False) is False

    def test_tpu_backend_compiles(self, monkeypatch):
        monkeypatch.setattr(kernels.jax, "default_backend", lambda: "tpu")
        assert resolve_interpret() is False

    def test_tpu_backend_refuses_interpreter(self, monkeypatch):
        monkeypatch.setattr(kernels.jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="interpret"):
            resolve_interpret(True)

    def test_wrappers_default_to_platform(self):
        """No wrapper pins ``interpret``: each defaults to None."""
        import inspect

        from repro.core.fusion import cute_matmul
        from repro.kernels.attention.ops import flash_attention
        from repro.kernels.matmul.ops import fused_matmul
        from repro.kernels.moe.ops import grouped_matmul
        from repro.kernels.quant.ops import quantize_rowwise
        from repro.kernels.rglru.ops import rglru_scan
        from repro.kernels.rwkv6.ops import rwkv6_decode_step
        for fn in (cute_matmul, flash_attention, fused_matmul,
                   grouped_matmul, quantize_rowwise, rglru_scan,
                   rwkv6_decode_step):
            sig = inspect.signature(getattr(fn, "__wrapped__", fn))
            assert sig.parameters["interpret"].default is None, fn

    def test_default_runs_on_cpu(self):
        from repro.kernels.quant.ops import quantize_rowwise
        from repro.kernels.quant.ref import quantize_rowwise_ref
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 128))
        q, s = quantize_rowwise(x)
        rq, rs = quantize_rowwise_ref(x)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(rq))
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs),
                                   rtol=1e-6)


class TestCompileCache:
    def test_environment_variable_wins(self, monkeypatch, tmp_path):
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        prev = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # nothing is set in code: JAX reads the variable itself
        assert jax.config.jax_compilation_cache_dir == prev

    def test_default_is_fixed_in_checkout(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        prev = jax.config.jax_compilation_cache_dir
        try:
            a = compile_cache.enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir == a
            b = compile_cache.enable_compile_cache()
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
        assert a == b == os.path.join(REPO, ".jax_cache")
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_default_is_the_same_in_another_process(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        env = {k: v for k, v in os.environ.items()
               if k != compile_cache.ENV_VAR}
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        out = subprocess.run(
            [sys.executable, "-c",
             "from repro.runtime.compile_cache import enable_compile_cache; "
             "print(enable_compile_cache())"],
            env=env, capture_output=True, text=True, check=True,
            cwd=os.path.dirname(REPO), timeout=120)
        assert out.stdout.strip() == compile_cache.DEFAULT_DIR
