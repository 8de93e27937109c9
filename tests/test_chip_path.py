"""What the program does differently on a TPU, checked on the CPU by
simulating the platform: no backend fallback, compiled (not interpreted)
Pallas, and a persistent compile cache at a predictable place."""
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.faults.harness import (BackendChaosError, maybe_chaos_fail,
                                  reset_fallback_warnings,
                                  run_with_fallback, strict_backend)
from repro.kernels import ops
from repro.obs import metrics
from repro.utils import env
from repro.utils.compile_cache import (DEFAULT_DIR, compile_cache_dir,
                                       enable_compile_cache)

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def on_tpu(monkeypatch):
    """The platform as a TPU process sees it (nothing is compiled here)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_STRICT_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)


def _fallbacks() -> float:
    return sum(c.value for c in metrics.REGISTRY.series("Counter",
                                                        "ops.fallback"))


# --- backend fallback --------------------------------------------------------

def test_strict_always_on_tpu_and_by_knob_elsewhere(monkeypatch, on_tpu):
    assert strict_backend()
    monkeypatch.setenv("REPRO_STRICT_BACKEND", "0")
    assert strict_backend()
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not strict_backend()
    monkeypatch.setenv("REPRO_STRICT_BACKEND", "1")
    assert strict_backend()


def test_tpu_dispatch_failure_raises_without_fallback(on_tpu):
    reset_fallback_warnings()
    calls = []

    def attempt(bk):
        calls.append(bk)
        maybe_chaos_fail(bk)
        return bk

    before = _fallbacks()
    with env.override(REPRO_CHAOS_BACKEND_FAIL="pallas_tiled"):
        with pytest.raises(BackendChaosError):
            run_with_fallback("op", "pallas_tiled", attempt)
    assert calls == ["pallas_tiled"]
    assert _fallbacks() == before


def test_tpu_kernel_op_failure_raises(on_tpu):
    """Through the public op: the failed backend is not retried on XLA."""
    rng = np.random.default_rng(5)
    n = 9
    nh = rng.integers(0, n, (n, n)).astype(np.int32)
    nh[np.arange(n), np.arange(n)] = np.arange(n)
    load0 = rng.random((n, n)).astype(np.float32)
    before = _fallbacks()
    with env.override(REPRO_CHAOS_BACKEND_FAIL="xla_blocked"):
        with pytest.raises(BackendChaosError, match="xla_blocked"):
            ops.load_propagate(jnp.asarray(nh), jnp.asarray(load0),
                               max_hops=4, backend="xla_blocked")
    assert _fallbacks() == before


# --- interpret mode ----------------------------------------------------------

def test_pallas_compiles_on_tpu_and_interprets_elsewhere(monkeypatch,
                                                         on_tpu):
    assert not ops.interpret_mode()
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert ops.interpret_mode()
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops.interpret_mode()
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert not ops.interpret_mode()


@pytest.mark.parametrize("backend,n,want", [
    ("pallas", 64, None), ("xla", 64, None),
    ("pallas_tiled", 576, ops.LANE_TILE),
    ("pallas_tiled_interpret", 256, ops.LANE_TILE),
])
def test_load_prop_tile_per_backend(monkeypatch, backend, n, want):
    monkeypatch.delenv("REPRO_LOAD_PROP_TILE", raising=False)
    assert ops.load_prop_tile(backend, n, 16) == want
    monkeypatch.setenv("REPRO_LOAD_PROP_TILE", "256")
    assert ops.load_prop_tile(backend, n, 16) == (256 if want else None)


# --- persistent compile cache ------------------------------------------------

def test_compile_cache_honours_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)


def test_compile_cache_default_is_fixed_and_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = compile_cache_dir(), compile_cache_dir()
    assert first == second == str(DEFAULT_DIR)
    assert Path(first).parent == REPO
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_enable_compile_cache_points_jax_there(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
