"""The harness: what it refuses, what it finds by name, and a run driven
on the CPU whose timed path is broken underneath."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
from test_chip_reference import tiny, tiny_cfg  # noqa: E402

ROOT = harness.ROOT
RUN = os.path.join(HERE, "run.py")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _run_py(cwd, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "chip", "run.py"),
         "--workload", "yi6b.decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, capture_output=True, text=True,
        timeout=240, env={**os.environ, "JAX_PLATFORMS": "cpu",
                          **(env or {})})


def test_check_device_refuses_a_cpu():
    with pytest.raises(harness.Refused, match="cpu"):
        harness.check_device(1)


def test_run_refuses_a_cpu_and_prints_no_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr and "cpu" in p.stderr


def test_run_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "src/repro" in p.stderr


@pytest.mark.parametrize("name", [w["name"] for w in bench()["workloads"]])
def test_every_cell_resolves_by_name(name):
    cell = harness.resolve(bench(), name)
    cfg = harness.program_config(cell.conf)
    assert cfg.n_layers == cell.conf["num_hidden_layers"]
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.reader(m["name"]))
    assert harness.reference_module(cell.conf).program_params
    t = cell.traffic
    assert t["prompt_len"] + t["new_tokens"] <= \
        cell.conf["max_position_embeddings"]
    assert 0 < cell.limits["greedy_gap"]
    assert cell.limits["sample_requests"] >= 1


def test_program_config_refuses_a_wrong_width():
    conf = dict(harness.resolve(bench(), "yi6b.decode").conf)
    conf["intermediate_size"] = 11000
    with pytest.raises(harness.Refused, match="intermediate_size"):
        harness.program_config(conf)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_benchmark_json_keeps_its_format():
    b = bench()
    assert set(b) == KEYS
    assert b["paths"] == ["benchmarks/chip"]
    assert b["command"][1] == "benchmarks/chip/run.py"
    assert 1 <= b["run_seconds"] <= 51
    configs = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/chip/")
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


# ---------------------------------------------------------------------------
# A run on the CPU, sound and with a fault planted in the timed path.
# ---------------------------------------------------------------------------

def tiny_cell(loop):
    b = bench()
    if loop == "closed":
        t = {"loop": "closed", "batch": 4, "prompt_len": 32,
             "new_tokens": 8}
        name = "yi6b.decode"
    else:
        t = {"loop": "open", "rate_per_s": 40, "schedule_seed": 0,
             "max_batch": 4, "prompt_len": 32, "new_tokens": 8}
        name = "yi6b.decode"
    real = harness.resolve(b, name)
    return harness.Cell(name, 1, tiny("yi-6b"), t, real.limits,
                        real.end_to_end, real.per_layer)


def tiny_run(loop, seed=2 ** 31 + 3):
    import jax
    import time
    cell = tiny_cell(loop)
    jax.clear_caches()
    try:
        return harness.run(cell, tiny_cfg(cell.conf), seed, 0.5, False,
                           time.perf_counter())
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_a_sound_run_is_correct(loop):
    res = tiny_run(loop)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    names = {m["name"] for m in tiny_cell(loop).end_to_end}
    assert set(res["metrics"]) == names
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_an_altered_token_is_not_correct(monkeypatch):
    """The first token of every request is changed where it is sampled."""
    import jax.numpy as jnp
    from repro.serving import engine
    sample = engine.sample
    calls = []

    def altered(logits, key, temperature=0.0):
        tok = sample(logits, key, temperature)
        calls.append(1)
        if len(calls) == 1:             # the prefill's sample, when traced
            tok = (tok + 1) % logits.shape[-1]
        return tok.astype(jnp.int32)

    monkeypatch.setattr(engine, "sample", altered)
    res = tiny_run("closed")
    assert res["failed"] == 0
    assert res["checks"]["greedy_gap"]["value"] > \
        res["checks"]["greedy_gap"]["limit"]
    assert res["correct"] is False
