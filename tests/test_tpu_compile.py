"""Compile the serving main path for a described TPU v5e, without a chip.

The TPU compiler is installed beside the CPU backend: it compiles for a
chip that is described and not attached, and refuses what the chip would
refuse — tiling it cannot lower, more fast memory than a kernel may use,
a program that does not fit the device's memory. Nothing runs, so these
tests say nothing about results or times.

Shapes are phi3-mini-3.8b's published widths (32 query and KV heads of
96, d_model 3072, d_ff 8192) at the pool ``chip_smoke.py`` serves: 8
slots, ``max_len`` 512, ``block_len`` 16, 257 blocks with the trash
block. The decode step is also compiled at the chat cell's pool (12
slots, ``max_len`` 2048, ``block_len`` 256, 40 blocks), where the TPU
stores the pool block-minor. The topology is described in a module
fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

SLOTS, MAX_LEN, BLOCK_LEN = 8, 512, 16
MAX_BLOCKS = MAX_LEN // BLOCK_LEN
NUM_BLOCKS = SLOTS * MAX_BLOCKS + 1
HEADS, HEAD_DIM, D_MODEL, D_FF = 32, 96, 3072, 8192
VERIFY_Q = 5  # spec_tokens = 4, plus the committed token
# the chat cell's engine: its pool is stored with the block axis minor
CELL_SLOTS, CELL_MAX_LEN, CELL_BLOCK_LEN, CELL_BLOCKS = 12, 2048, 256, 40


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


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_stacked_paged_attention_kernel_compiles(one_chip, pool_dtype):
    """The layer-indexed decode kernel over a whole 32-layer stack at the
    chat cell's pool reads it in place: its pool operands are bitcasts of
    the arguments, so the program needs no scratch of a pool's size."""
    from repro.kernels.paged_attention import ops

    pool = _spec(one_chip, (32, CELL_BLOCKS, HEADS, CELL_BLOCK_LEN,
                            HEAD_DIM), pool_dtype)
    q = _spec(one_chip, (CELL_SLOTS, HEADS, 1, HEAD_DIM), jnp.bfloat16)
    table = _spec(one_chip, (CELL_SLOTS, CELL_MAX_LEN // CELL_BLOCK_LEN),
                  jnp.int32)
    lens = _spec(one_chip, (CELL_SLOTS,), jnp.int32)
    layer = _spec(one_chip, (), jnp.int32)
    if pool_dtype == jnp.int8:
        scale = _spec(one_chip, (32, CELL_BLOCKS), jnp.float32)
        def fn(q, k, v, t, n, ly, ks, vs):
            return ops.paged_attention_int8(
                q, k, v, t, n, k_scale=ks, v_scale=vs, layer=ly,
                backend="pallas")
        args = [q, pool, pool, table, lens, layer, scale, scale]
    else:
        def fn(q, k, v, t, n, ly):
            return ops.paged_attention(q, k, v, t, n, layer=ly,
                                       backend="pallas")
        args = [q, pool, pool, table, lens, layer]
    compiled, calls = _custom_calls(fn, *args)
    assert calls >= 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert _pool_traffic(compiled.as_text(), pool.shape) == []


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


_HLO_DEF = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* "
                      r"([\w-]+)\(([^)]*)\)")


def _pool_traffic(hlo: str, pool_shape) -> list:
    """Instructions of a compiled program that move a whole pool or a
    pool layer: a copy or dynamic-slice whose result has that size, or a
    dynamic-update-slice whose update does (an update of a few tokens
    into the pool, in place, is what the decode step is for). Shapes are
    compared with their unit dims dropped and their two minor dims in
    either order, in fused computations too."""
    def key(dims):
        dims = tuple(d for d in dims if d != 1)
        return dims[:-2] + tuple(sorted(dims[-2:]))

    pool = tuple(pool_shape)
    bad = {key(pool), key(pool[1:])}
    shapes, hits = {}, []
    for line in hlo.splitlines():
        m = _HLO_DEF.match(line)
        if not m:
            continue
        name, _, dims, op, operands = m.groups()
        dims = tuple(int(d) for d in dims.split(",") if d)
        shapes[name] = dims
        if op in ("copy", "copy-start", "dynamic-slice") and key(dims) in bad:
            hits.append(line.strip()[:160])
        elif op == "dynamic-update-slice":
            update = operands.split(",")[1].strip().lstrip("%")
            if key(shapes.get(update, ())) in bad:
                hits.append(line.strip()[:160])
    return hits


def _decode_step_compiled(one_chip, monkeypatch, *, quantized, slots,
                          max_len, block_len, num_blocks):
    """The whole 32-layer phi3-mini decode step, cache donated as the
    engine donates it. The W8A8 GEMMs pick their backend from the
    platform, which here is the CPU, so the test steers them to the TPU's
    choice."""
    import dataclasses

    import repro.kernels
    from repro import configs
    from repro.models import registry, schema as schema_lib
    from repro.models.cache import PagedLayout

    monkeypatch.setattr(repro.kernels, "platform_backend", lambda: "pallas")
    cfg = configs.get_config("phi3-mini-3.8b")
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model, cfg.d_ff) == (
        HEADS, HEADS, HEAD_DIM, D_MODEL, D_FF)
    cfg = dataclasses.replace(cfg, serve_quant=quantized)
    arch = registry.build(cfg)
    params = schema_lib.abstract_params(arch.schema(), cfg.compute_dtype)
    qparams = (jax.eval_shape(arch.quantize_params, params) if quantized
               else None)
    layout = PagedLayout(block_len, num_blocks, max_len)
    cache = jax.eval_shape(lambda: arch.init_paged_cache(slots, layout))
    args = [_specs(one_chip, t) for t in (params, qparams, cache)]
    args += [_spec(one_chip, (slots,), jnp.int32),
             _spec(one_chip, (slots, max_len // block_len), jnp.int32)]

    def step(p, qp, cache, tokens, table):
        return arch.paged_decode_step(p, cache, tokens, table, qparams=qp,
                                      attn_backend="pallas")

    compiled, calls = _custom_calls(step, *args, donate=(2,))
    pool = cache["stacks"][0]["k"]
    layer_kv = 2 * pool.size // pool.shape[0] * pool.dtype.itemsize
    return compiled, calls, pool.shape, layer_kv


def test_paged_decode_step_updates_pool_in_place(one_chip, monkeypatch):
    """The bf16 decode step at the chat cell's pool, which the TPU stores
    block-minor: the stacked pool is updated in place through the layer
    loop, so the program's scratch is below one layer's K+V (a pool-sized
    buffer when each layer was sliced out of the scan's inputs and
    stacked back into its outputs), and no copy, slice or update of a
    pool or a pool layer is left in it."""
    compiled, calls, pool_shape, layer_kv = _decode_step_compiled(
        one_chip, monkeypatch, quantized=False, slots=CELL_SLOTS,
        max_len=CELL_MAX_LEN, block_len=CELL_BLOCK_LEN,
        num_blocks=CELL_BLOCKS)
    assert calls >= 1
    mem = compiled.memory_analysis()
    print(f"scratch {mem.temp_size_in_bytes} B, one layer's K+V "
          f"{layer_kv} B")
    assert mem.temp_size_in_bytes < layer_kv
    assert _pool_traffic(compiled.as_text(), pool_shape) == []


def test_int8_paged_decode_step_fits_one_chip(one_chip, monkeypatch):
    """The whole 32-layer int8 decode step at the smoke's pool, with the
    cache donated as the engine donates it: the compiler refuses a
    program that does not fit the chip's memory, and the pool is updated
    in place (scratch below one layer's K+V)."""
    compiled, calls, pool_shape, layer_kv = _decode_step_compiled(
        one_chip, monkeypatch, quantized=True, slots=SLOTS, max_len=MAX_LEN,
        block_len=BLOCK_LEN, num_blocks=NUM_BLOCKS)
    # one paged-attention kernel and seven GEMMs in the scanned layer body
    assert calls >= 8
    mem = compiled.memory_analysis()
    print(f"arguments {mem.argument_size_in_bytes} B, outputs "
          f"{mem.output_size_in_bytes} B, aliased {mem.alias_size_in_bytes}"
          f" B, scratch {mem.temp_size_in_bytes} B")
    assert mem.temp_size_in_bytes < layer_kv
    assert _pool_traffic(compiled.as_text(), pool_shape) == []
