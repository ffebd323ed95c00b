"""``chip_smoke.py`` on the CPU: its phases run end to end at a tiny size, it
refuses to run without a TPU (and without the rest of the checkout), and the
compile-cache helper it and ``connect`` call places the cache where it
should."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke as cs
from repro import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_phases_a_and_c_run_end_to_end_tiny(tmp_path):
    a = cs.phase_a(
        0, str(tmp_path), train_rows=256, db_rows=3000, n_requests=3,
        min_rows=50, max_rows=400, n_estimators=3, depth=2,
        require_kernels=False,
    )
    assert len(a["results"]) == 3
    cs.phase_c(a, str(tmp_path))


def test_phase_b_runs_end_to_end_tiny():
    cs.phase_b(0, dim_rows=512, request_rows=(100, 300), train_rows=256,
               require_kernels=False)


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300,
    )


def test_smoke_fails_without_a_tpu():
    proc = _run_smoke(REPO, "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_smoke_fails_outside_the_checkout(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_smoke(str(tmp_path), "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_kernel_checks_read_explain_and_lowered_text():
    explain = (
        "* MLtoDNN fused featurize kernel: features\n"
        "* MLtoDNN tree ensemble score: gemm (tree_gemm kernel)\n"
        "  Join[d] on fk=k\n"
        "    -> tensor/jnp: argsort+searchsorted gather (duplicate keys)\n"
        "  Aggregate[n=count(x)]\n"
        "    -> tensor/kernel: segment_agg, filter folded in as mask\n"
    )
    assert cs.placed_kernels(explain) == {"featurize", "tree_gemm",
                                          "segment_agg"}
    lowered = (
        'stablehlo.custom_call @tpu_custom_call(%0) {kernel_name = '
        '"tree_gemm"} ... @tpu_custom_call(%1) {kernel_name = "featurize"}'
    )
    assert cs.lowered_kernels(lowered) == {"tree_gemm", "featurize"}
    assert cs.lowered_kernels('kernel_name = "tree_gemm"') == set()


@pytest.fixture()
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_respects_the_environment(monkeypatch, cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/jax-cache")
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.configure_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir is None  # JAX reads the env


def test_compile_cache_defaults_to_one_fixed_checkout_dir(
    monkeypatch, cache_config
):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    for _ in range(2):
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.configure_compile_cache() == os.path.join(
            REPO, ".jax_cache"
        )
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache"
        )
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
