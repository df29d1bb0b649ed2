"""The harness end to end at a tiny size on the CPU: a whole run passes
its check; the same run with the served path broken underneath, or with
the lower-precision control in the program's place, does not; and the
command refuses to run without a TPU or without the program."""
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench import run
from bench.tests.cpu_cell import PEAKS, tiny_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(cell, seed=5, modes=("f32",)):
    return run.run_cell(cell, seed, 2.0, False, PEAKS, jax.devices(),
                        modes=modes, t_start=time.monotonic())


@pytest.mark.parametrize("kind", ["open_poisson", "backlog", "closed"])
def test_tiny_run_is_correct(kind):
    res = _run(tiny_cell(kind))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["window"]["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"
    names = {m["name"] for m in tiny_cell(kind).end_to_end}
    assert "setup_s" in res["metrics"]
    assert set(res["metrics"]) <= names
    if kind == "backlog":
        assert res["metrics"]["output_tokens_per_s"]["value"] > 0


def _alter_tokens(monkeypatch):
    from repro.serving.engine import ServingEngine
    orig = ServingEngine._pick
    n = [0]

    def pick(self, logits, req):
        tok = orig(self, logits, req)
        n[0] += 1
        return (tok + 1) % self.cfg.vocab_size if n[0] % 5 == 0 else tok
    monkeypatch.setattr(ServingEngine, "_pick", pick)


def _state_unchanged(monkeypatch):
    from repro.serving.kv_cache import PagedKVCache
    monkeypatch.setattr(PagedKVCache, "bump", lambda self, slot: None)


def _half_batch(monkeypatch):
    import jax.numpy as jnp
    from repro.serving.engine import ServingEngine
    orig = ServingEngine._cohort_fn

    def cohort_fn(self, bc):
        fn = orig(self, bc)

        def half(*args):
            logits, pool = fn(*args)
            if bc > 1:
                h = bc // 2
                logits = jnp.concatenate([logits[:h], logits[:h]], axis=0)
            return logits, pool
        return half
    monkeypatch.setattr(ServingEngine, "_cohort_fn", cohort_fn)


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged,
                                   _half_batch])
def test_broken_served_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    cell = tiny_cell("backlog")
    # a fault confined to some cohort rows shows only in requests served
    # there: check enough of them that every row is in the sample
    cell.checks["requests"] = 16
    res = _run(cell)
    assert not res["correct"]
    c = res["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]


def test_lower_precision_control_is_not_correct():
    # a vocabulary wide enough for near ties, so that rounding the
    # weights to fp8 moves the first token where bf16 serving does not
    cell = tiny_cell("backlog", output_tokens={"dist": "uniform", "min": 12,
                                               "max": 24})
    for key in ("config", "overrides"):
        cell.config[key]["vocab_size"] = 32768
    cell.checks["pad_outputs"] = 32
    cell.checks["limits"]["max_logit_gap"] = 0.03
    res = _run(cell, seed=3, modes=("f32", "fp8"))
    limit = cell.checks["limits"]["max_logit_gap"]
    assert max(res["window"]["gap_f32"]) <= limit
    assert max(res["window"]["gap_fp8"]) > limit


def _cmd(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "llava-camera-ondemand", "--seed", str(2**31 + 9), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _cmd(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bench_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cmd(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_seed_key_takes_wide_seeds():
    a = np.asarray(jax.random.key_data(run.seed_key(2**33 + 1)))
    b = np.asarray(jax.random.key_data(run.seed_key(1)))
    assert not np.array_equal(a, b)
