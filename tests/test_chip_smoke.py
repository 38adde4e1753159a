"""``chip_smoke.py`` off the chip: its phases at tiny size on the CPU, its
refusal to report without a TPU, and the compile-cache helper the entry
points share."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import tiny_config
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


def test_phases_run_and_check_at_tiny_size():
    """Both phases through the same entry points and checks as on the
    chip, at a fig-3 round of 8-row blocks and the tiny qwen2 config."""
    with chip_smoke.Probe() as probe:
        chip_smoke.phase_rounds(probe, 24 * 8, 256, 128)
        chip_smoke.phase_serve(probe, tiny_config("qwen2-7b"), n_requests=2,
                               prompt_len=(4, 6), gen=3)
    recs = probe.records
    assert set(recs) == {"A.fused_round", "A.xla_twin", "A.encrypted_paper",
                         "A.encrypted_stream", "A.anytime_curve",
                         "B.serve_deadline_all", "B.exact_logits"}
    for rec in recs.values():
        assert rec["wall_s"] >= rec["compile_s"] >= 0
    assert recs["A.encrypted_stream"]["bit_identical_to_plain"]
    assert recs["B.serve_deadline_all"]["generated"] == 2 * 3
    # the probe names the programs it saw compile; no kernels off the chip
    assert recs["A.fused_round"]["tpu_custom_call"] == {}


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_tpu(where, tmp_path):
    """No TPU: exit non-zero and never print the ok line — from the repo
    root, and from a directory holding only the script."""
    if where == "repo":
        cwd = ROOT
    else:
        cwd = tmp_path
        shutil.copy(ROOT / "chip_smoke.py", cwd)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout


@pytest.fixture
def cache_config():
    """Restore JAX's compilation-cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    where = compile_cache.enable_compile_cache()
    assert where == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == where


def test_compile_cache_follows_env(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set nothing
    # JAX reads the variable itself: a fresh process compiles into it
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    run = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode == 0, run.stderr
    assert run.stdout.strip() == str(tmp_path)
    assert any(p.name.startswith("jit__lambda") for p in tmp_path.iterdir())
