"""Compile the serving main path for a described TPU v5e, without a chip.

The TPU compiler is installed beside the CPU backend: it compiles for a
chip that is described and not attached, and refuses what the chip would
refuse — tiling it cannot lower, more fast memory than a kernel may use,
a program that does not fit the device's memory. Nothing runs, so these
tests say nothing about results or times.

Shapes are phi3-mini-3.8b's published widths (32 query and KV heads of
96, d_model 3072, d_ff 8192) at the pool ``chip_smoke.py`` serves: 8
slots, ``max_len`` 512, ``block_len`` 16, 257 blocks with the trash
block. The topology is described in a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

SLOTS, MAX_LEN, BLOCK_LEN = 8, 512, 16
MAX_BLOCKS = MAX_LEN // BLOCK_LEN
NUM_BLOCKS = SLOTS * MAX_BLOCKS + 1
HEADS, HEAD_DIM, D_MODEL, D_FF = 32, 96, 3072, 8192
VERIFY_Q = 5  # spec_tokens = 4, plus the committed token


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _specs(one_chip, tree):
    return jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype), tree)


def _custom_calls(fn, *args, donate=()):
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    return compiled, compiled.as_text().count("tpu_custom_call")


def _pools(one_chip, dtype):
    shape = (NUM_BLOCKS, HEADS, BLOCK_LEN, HEAD_DIM)
    return _spec(one_chip, shape, dtype), _spec(one_chip, shape, dtype)


@pytest.mark.parametrize("op,qlen,pool_dtype", [
    ("paged_attention", 1, jnp.bfloat16),
    ("paged_attention_int8", 1, jnp.int8),
    ("paged_attention_verify", VERIFY_Q, jnp.bfloat16),
    ("paged_attention_verify_int8", VERIFY_Q, jnp.int8),
])
def test_paged_attention_kernel_compiles(one_chip, op, qlen, pool_dtype):
    from repro.kernels.paged_attention import ops

    q = _spec(one_chip, (SLOTS, HEADS, qlen, HEAD_DIM), jnp.bfloat16)
    kp, vp = _pools(one_chip, pool_dtype)
    table = _spec(one_chip, (SLOTS, MAX_BLOCKS), jnp.int32)
    lens = _spec(one_chip, (SLOTS,), jnp.int32)
    args = [q, kp, vp, table, lens]
    if pool_dtype == jnp.int8:
        scale = _spec(one_chip, (NUM_BLOCKS,), jnp.float32)
        fn = lambda q, k, v, t, n, ks, vs: getattr(ops, op)(  # noqa: E731
            q, k, v, t, n, k_scale=ks, v_scale=vs, backend="pallas")
        args += [scale, scale]
    else:
        fn = functools.partial(getattr(ops, op), backend="pallas")
    _, calls = _custom_calls(fn, *args)
    assert calls >= 1


# decode runs M = slots rows; a prefill of a 128-token bucket M = 128;
# (K, N) are the Q/K/V/O projections and the MLP's up and down GEMMs
@pytest.mark.parametrize("m", [SLOTS, 128])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_MODEL), (D_MODEL, D_FF),
                                 (D_FF, D_MODEL)])
def test_int8_gemm_kernel_compiles(one_chip, m, k, n):
    from repro.kernels.int8_gemm.ops import QuantizedLinearParams, int8_gemm

    x = _spec(one_chip, (m, k), jnp.int8)
    p = QuantizedLinearParams(
        w_q=_spec(one_chip, (k, n), jnp.int8),
        bias=_spec(one_chip, (n,), jnp.int32),
        mult=_spec(one_chip, (n,), jnp.int32),
        shift=_spec(one_chip, (n,), jnp.int32))
    compiled, calls = _custom_calls(
        functools.partial(int8_gemm, backend="pallas"), x, p)
    assert calls >= 1
    assert compiled.out_info.shape == (m, n)


def test_int8_paged_decode_step_fits_one_chip(one_chip, monkeypatch):
    """The whole 32-layer int8 decode step at the smoke's pool, with the
    cache donated as the engine donates it: the compiler refuses a
    program that does not fit the chip's memory. The W8A8 GEMMs pick
    their backend from the platform, which here is the CPU, so the test
    steers them to the TPU's choice."""
    import repro.kernels
    from repro import configs
    from repro.models import registry, schema as schema_lib
    from repro.models.cache import PagedLayout

    monkeypatch.setattr(repro.kernels, "platform_backend", lambda: "pallas")
    cfg = configs.get_config("phi3-mini-3.8b")
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model, cfg.d_ff) == (
        HEADS, HEADS, HEAD_DIM, D_MODEL, D_FF)
    arch = registry.build(cfg)
    params = schema_lib.abstract_params(arch.schema(), cfg.compute_dtype)
    qparams = jax.eval_shape(arch.quantize_params, params)
    layout = PagedLayout(BLOCK_LEN, NUM_BLOCKS, MAX_LEN)
    cache = jax.eval_shape(lambda: arch.init_paged_cache(SLOTS, layout))
    args = [_specs(one_chip, t) for t in (params, qparams, cache)]
    args += [_spec(one_chip, (SLOTS,), jnp.int32),
             _spec(one_chip, (SLOTS, MAX_BLOCKS), jnp.int32)]

    def step(p, qp, cache, tokens, table):
        return arch.paged_decode_step(p, cache, tokens, table, qparams=qp,
                                      attn_backend="pallas")

    compiled, calls = _custom_calls(step, *args, donate=(2,))
    # one paged-attention kernel and seven GEMMs in the scanned layer body
    assert calls >= 8
    mem = compiled.memory_analysis()
    print(f"arguments {mem.argument_size_in_bytes} B, outputs "
          f"{mem.output_size_in_bytes} B, aliased {mem.alias_size_in_bytes}"
          f" B, scratch {mem.temp_size_in_bytes} B")
