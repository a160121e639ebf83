"""The serving entry construction: ``launch.serve.build_engine``, kernel
backends from the platform, the compile-cache setting, and the paged
backend's table upload."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.serve import build_engine
from repro.serve import EngineConfig


def test_build_engine_params_in_config_dtype_and_serves():
    model = configs.smoke_config("phi3-mini-3.8b")
    ec = EngineConfig(backend="paged", slots=2, max_len=32, block_len=8)
    engine = build_engine(model, ec)
    # the CPU reports no memory, so the pool stays as the config sizes it
    assert engine.backend.pool_sizing == {}
    assert engine.backend.layout.num_blocks == 2 * 32 // 8 + 1
    assert {x.dtype for x in jax.tree.leaves(engine.params)} == {
        jnp.dtype(model.compute_dtype)}
    h = engine.add_request(np.arange(5, dtype=np.int32), max_new_tokens=3)
    engine.run_until_drained()
    assert len(engine.request(h).output) == 3


class _DeviceWithMemory:
    """Reports ``limit`` bytes, ``in_use`` of them taken, and
    ``probe_bytes`` more at every second reading (the probe pool)."""

    def __init__(self, limit, in_use, probe_bytes):
        self.limit, self.in_use, self.probe_bytes = limit, in_use, probe_bytes
        self.readings = 0

    def memory_stats(self):
        self.readings += 1
        grown = self.probe_bytes if self.readings % 2 == 0 else 0
        return {"bytes_limit": self.limit, "bytes_in_use": self.in_use + grown}


@pytest.mark.parametrize("limit,fits", [(10 << 30, True), (5 << 30, False)])
def test_paged_pool_fits_device_memory(monkeypatch, limit, fits):
    """num_blocks=None on a device that reports its memory: the pool is
    what the config addresses or what half the free memory holds, the
    smaller; no room at all fails at construction."""
    from repro.serve.backends import POOL_RESERVE_BYTES

    engine = build_engine(configs.smoke_config("phi3-mini-3.8b"),
                          EngineConfig(backend="paged", slots=2,
                                       max_len=32, block_len=8))
    b = engine.backend
    in_use, block = (4 << 30) - 100, 1 << 20
    dev = _DeviceWithMemory(limit, in_use, 64 * block)  # probe: 64 blocks
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    if not fits:
        with pytest.raises(RuntimeError, match="no room"):
            b._fit_num_blocks(1000)
        return
    fit = (limit - in_use - POOL_RESERVE_BYTES) // 2 // block
    assert b._fit_num_blocks(10 * fit) == fit
    assert b._fit_num_blocks(9) == 9
    assert b.pool_sizing == dict(
        num_blocks=9, fit_blocks=fit, want_blocks=9, block_bytes=block,
        bytes_limit=limit, in_use_bytes=in_use)


def test_build_engine_rejects_unsupported_backend():
    with pytest.raises(ValueError, match="unsupported"):
        build_engine(configs.smoke_config("mamba2-2.7b"),
                     EngineConfig(backend="paged"))


def test_kernel_backend_comes_from_the_platform():
    from repro.kernels import platform_backend, resolve_backend

    expect = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert platform_backend() == expect
    assert resolve_backend(None) == expect
    assert resolve_backend("interpret") == "interpret"


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the code sets no other directory
    assert jax.config.jax_compilation_cache_dir == before
    # the fallback is one fixed directory inside the checkout
    root = compile_cache.DEFAULT_DIR.parent
    assert (root / "src" / "repro" / "launch").is_dir()
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_paged_tables_upload_a_snapshot():
    """The host block tables are written in place while a dispatch that
    read them may still run, so the device view must be a copy."""
    model = configs.smoke_config("phi3-mini-3.8b")
    engine = build_engine(model, EngineConfig(
        backend="paged", slots=2, max_len=32, block_len=8))
    b = engine.backend
    dev = b._tables()
    b.table[0, 0] = 7
    assert int(dev[0, 0]) == 0
    b._touch_tables()
    assert int(b._tables()[0, 0]) == 7
