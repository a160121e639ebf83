"""Pallas TPU kernel: paged gather-decode attention over a block table.

The serving analogue of CHIMERA's banked shared-L2 island: KV state lives
in a shared pool of fixed-size blocks (``[num_blocks, Hkv, block_len, D]``)
instead of one dense per-slot arena, and each decode row walks its own
block list. The kernel never materializes the gathered KV — the grid's
innermost dimension iterates over table entries and the **scalar-prefetched
block table drives the BlockSpec index maps**, so each (row, head, i) step
DMAs exactly one pool block into VMEM (the software version of the island's
interleaved bank fetch).

Dataflow per (row b, kv-head h):
    for i in range(max_blocks):                 # innermost grid dim
        K_blk = k_pool[table[b, i], h]          # DMA via index_map
        s     = Q_row · K_blkᵀ  (+ length/window mask)
        flash-update (m, l, acc)                # f32 running softmax
    out[b, h] = acc / l

Grouped GQA: the q "row" is the [group, D] bundle of query heads sharing
kv-head h, so pool blocks are read once per kv head, not per query head.

Ring tables (sliding-window layers): the table may cover a *rotating*
window of blocks instead of the full history. A third scalar-prefetched
vector ``start`` gives each row the absolute position of table entry 0's
first row, so masking is always by absolute position — full-history
callers pass zeros and the two layouts share one kernel.

Contract: allclose against ``ref.paged_attention_ref`` (same masking; the
flash accumulation only reorders f32 additions).

**Stacked pools** (``layer``): the decode kernels also read a model's
whole per-pattern-position stack ``[L, N, Hkv, block_len, D]`` in place.
``layer`` (an int32 scalar, the decode step's loop index) rides in scalar
prefetch beside the table and the index maps return ``(layer, table[b,
i], h, 0, 0)``; grid, block shapes, flash math and the one DMA per table
entry are those of a single layer's pool, which is read as a stack of
one. Blocks are DMA'd in the order the TPU stores the pool
(``block_minor``): where it keeps the block axis minor (``block_len`` a
multiple of 128, ``D`` not), the kernel takes ``[D, block_len]`` blocks
and contracts over the other axis, so the pool reaches it as a bitcast
and is never relaid out.

**Int8 block pools** (``paged_attention_int8_pallas``): the quantized
serving layout stores K/V blocks as int8 plus per-block scales, so the
kernel DMAs *half* the bytes per block and dequantizes on the fly — q is
requantized once outside (static ``Q_SCALE``), each block contributes an
exact int8·int8 → int32 score dot (the ITA pipeline's quantized-operand /
integer-accumulation discipline), and the int32 scores are dequantized
through ``Q_SCALE · k_scale[block]`` into the same f32 flash softmax. The
per-block scales ride in scalar prefetch next to the table. Numerical
contract: allclose against ``ref.paged_attention_int8_dequant_ref`` (flash
reordering only); the ITA *integer*-softmax oracle differs by its own
quantization error (~1%) because a streamed kernel cannot take the global
integer max before exponentiating.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    table_ref, lens_ref, start_ref, layer_ref,  # scalar prefetch (SMEM)
    q_ref, k_ref, v_ref,            # blocks picked by index maps
    o_ref,
    m_ref, l_ref, acc_ref,          # VMEM scratch
    *, block_len: int, group: int, window: Optional[int],
    transposed: bool,
):
    del layer_ref  # read by the index maps only
    b = pl.program_id(0)
    i = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[b]
    # absolute position of table entry i's first row: ring tables hand the
    # kernel a window-start vector (entry 0 = oldest live block); full-
    # history tables pass zeros and reduce to position == table offset
    row0 = start_ref[b] + i * block_len
    # skip table entries entirely past the row's valid length
    @pl.when(row0 < length)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)    # [group, D] (pre-scaled)
        # [block_len, D], or [D, block_len] where the pool is block-minor
        k = k_ref[0, 0, 0].astype(jnp.float32)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (0 if transposed else 1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [group, block_len]
        pos = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_len), 1)
        mask = pos < length
        if window is not None:
            mask &= pos >= length - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                    # [group, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                 # [group, block_len]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (1 if transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def _finish():
        # fully-masked rows (len 0: empty serve slots) produce zeros
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def block_minor(block_len: int, d: int) -> bool:
    """Whether a TPU stores a ``[..., block_len, D]`` pool with the block
    axis minor (physically ``[..., D, block_len]``).

    The TPU's default layout keeps a multiple of 128 lanes minor: a head
    width short of one (96, 80, 64) behind a block of 128, 256, ... rows
    is stored transposed, so no lane is padding. A kernel must read the
    pool in that order: its operands are row-major, and any other view
    makes the compiler relayout the whole pool before the call.
    """
    return d % 128 != 0 and block_len % 128 == 0


def _stacked(k_pool, v_pool, layer):
    """(k, v, layer, transposed): the pools as ``[L, N, Hkv, ., .]``
    stacks in their stored order, and the layer as a ``[1]`` int32 vector
    for scalar prefetch. A 4-D pool is a stack of one layer."""
    if layer is None:
        k_pool, v_pool = k_pool[None], v_pool[None]
        layer = 0
    blk, d = k_pool.shape[-2:]
    transposed = block_minor(blk, d)
    if transposed:
        # a bitcast where the pool already lies block-minor
        k_pool, v_pool = (jnp.swapaxes(a, -1, -2) for a in (k_pool, v_pool))
    return (k_pool, v_pool,
            jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), transposed)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret"))
def paged_attention_pallas(
    q: jax.Array,            # [B, Hq, 1, D] float (post-RoPE)
    k_pool: jax.Array,       # [N, Hkv, block_len, D], or [L, N, ...]
    v_pool: jax.Array,       # like k_pool
    block_table: jax.Array,  # [B, M] int32
    lens: jax.Array,         # [B] int32
    *,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,  # [B] int32 abs position of entry 0
    layer: Optional[jax.Array] = None,  # int32 scalar: the stack's layer
    interpret: bool = False,
) -> jax.Array:
    b, hq, _, d = q.shape
    hkv, blk = k_pool.shape[-3], k_pool.shape[-2]
    m = block_table.shape[1]
    group = hq // hkv
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    qg = (q.astype(jnp.float32) * (d ** -0.5)).reshape(b, hkv, group, d)
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    k_pool, v_pool, layer, transposed = _stacked(k_pool, v_pool, layer)
    kv_block = (1, 1, 1) + k_pool.shape[-2:]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block table + lens + window start + layer drive index maps and
        # masking
        num_scalar_prefetch=4,
        grid=(b, hkv, m),
        in_specs=[
            pl.BlockSpec((1, 1, group, d),
                         lambda bi, h, i, tbl, ln, st, ly: (bi, h, 0, 0)),
            pl.BlockSpec(kv_block,
                         lambda bi, h, i, tbl, ln, st, ly:
                         (ly[0], tbl[bi, i], h, 0, 0)),
            pl.BlockSpec(kv_block,
                         lambda bi, h, i, tbl, ln, st, ly:
                         (ly[0], tbl[bi, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, d),
            lambda bi, h, i, tbl, ln, st, ly: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, block_len=blk, group=group, window=window,
        transposed=transposed)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), jnp.asarray(lens, jnp.int32),
      jnp.asarray(start, jnp.int32), layer, qg, k_pool, v_pool)
    return out.reshape(b, hq, 1, d)


# ---------------------------------------------------------------------------
# Int8 block pools: fused dequantizing decode kernel
# ---------------------------------------------------------------------------


def _paged_int8_kernel(
    table_ref, lens_ref, start_ref, layer_ref, ks_ref, vs_ref,  # SMEM
    q_ref, k_ref, v_ref,            # q int8 row, int8 pool blocks
    o_ref,
    m_ref, l_ref, acc_ref,          # VMEM scratch (f32)
    *, block_len: int, group: int, window: Optional[int], q_scale: float,
    transposed: bool,
):
    del layer_ref  # read by the index maps only
    b = pl.program_id(0)
    i = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[b]
    row0 = start_ref[b] + i * block_len
    blk_id = table_ref[b, i]

    @pl.when(row0 < length)
    def _block():
        q8 = q_ref[0, 0]                       # [group, D] int8
        k8 = k_ref[0, 0, 0]                    # int8, as in the float kernel
        v8 = v_ref[0, 0, 0]
        # exact integer score dot (the ITA quantized-operand discipline),
        # dequantized through the static q scale × this block's k scale
        s32 = jax.lax.dot_general(
            q8, k8, (((1,), (0 if transposed else 1,)), ((), ())),
            preferred_element_type=jnp.int32)  # [group, block_len]
        s = s32.astype(jnp.float32) * (q_scale * ks_ref[blk_id])
        pos = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_len), 1)
        mask = pos < length
        if window is not None:
            mask &= pos >= length - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                    # [group, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                 # [group, block_len]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        # v dequant folds into the partial product: the scale is constant
        # within a block, so (p · v8)·vs ≡ p · (vs·v8)
        pv = jax.lax.dot_general(
            p, v8.astype(jnp.float32),
            (((1,), (1 if transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv * vs_ref[blk_id]
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def _finish():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "q_scale", "interpret"))
def paged_attention_int8_pallas(
    q: jax.Array,            # [B, Hq, 1, D] float (post-RoPE)
    k_pool: jax.Array,       # [N, Hkv, block_len, D] int8, or [L, N, ...]
    v_pool: jax.Array,       # like k_pool
    block_table: jax.Array,  # [B, M] int32
    lens: jax.Array,         # [B] int32
    k_scale: jax.Array,      # [N] f32 per-block scales (the layer's)
    v_scale: jax.Array,      # [N] f32
    *,
    q_scale: float,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,  # [B] int32 abs position of entry 0
    layer: Optional[jax.Array] = None,  # int32 scalar: the stack's layer
    interpret: bool = False,
) -> jax.Array:
    b, hq, _, d = q.shape
    hkv, blk = k_pool.shape[-3], k_pool.shape[-2]
    m = block_table.shape[1]
    group = hq // hkv
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    if k_pool.dtype != jnp.int8 or v_pool.dtype != jnp.int8:
        raise ValueError(
            f"int8 kernel needs int8 pools, got {k_pool.dtype}/{v_pool.dtype}")
    qs = q.astype(jnp.float32) * (d ** -0.5)
    q8 = jnp.clip(jnp.round(qs / q_scale), -127, 127).astype(jnp.int8)
    qg = q8.reshape(b, hkv, group, d)
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    k_pool, v_pool, layer, transposed = _stacked(k_pool, v_pool, layer)
    kv_block = (1, 1, 1) + k_pool.shape[-2:]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # table + lens + start + layer + per-block k/v scales: the scales
        # are tiny ([N] f32) and needed at score/accumulate time, so they
        # ride in SMEM with the rest of the prefetch set
        num_scalar_prefetch=6,
        grid=(b, hkv, m),
        in_specs=[
            pl.BlockSpec((1, 1, group, d),
                         lambda bi, h, i, tbl, ln, st, ly, ks, vs:
                         (bi, h, 0, 0)),
            pl.BlockSpec(kv_block,
                         lambda bi, h, i, tbl, ln, st, ly, ks, vs:
                         (ly[0], tbl[bi, i], h, 0, 0)),
            pl.BlockSpec(kv_block,
                         lambda bi, h, i, tbl, ln, st, ly, ks, vs:
                         (ly[0], tbl[bi, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, d),
            lambda bi, h, i, tbl, ln, st, ly, ks, vs: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_int8_kernel, block_len=blk, group=group, window=window,
        q_scale=q_scale, transposed=transposed)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), jnp.asarray(lens, jnp.int32),
      jnp.asarray(start, jnp.int32), layer,
      jnp.asarray(k_scale, jnp.float32), jnp.asarray(v_scale, jnp.float32),
      qg, k_pool, v_pool)
    return out.reshape(b, hq, 1, d)


# ---------------------------------------------------------------------------
# Small-q verify kernels (speculative decoding)
# ---------------------------------------------------------------------------
#
# The verify step sits between decode (q=1) and prefill: each row carries
# Q = spec_tokens + 1 query positions — the last committed token plus the
# drafts — whose K/V were just written at positions len-1 … len-1+Q-1.
# Query row j attends ``lens + j`` keys. Folding Q into the grouped-row
# axis reuses the decode kernel's dataflow unchanged: the q "row" becomes
# the [group·Q, D] bundle, each flash row gets a per-row effective length
# ``lens + (row % Q)``, and pool blocks are still DMA'd exactly once per
# (row, kv-head) — the whole point: k+1 tokens scored per pool sweep.
#
# A block is skipped only when it is past *every* row's length
# (``row0 < length + Q - 1``). Blocks fully masked for a given flash row
# are exact no-ops for that row: the masked scores are NEG_INF, so either
# the row's running max is already finite (p underflows to exact 0, alpha
# is exp(0)=1) or it is still NEG_INF and the later first valid block's
# alpha = exp(NEG_INF − finite) rescales the placeholder sums by exact 0.
# Row j=0 therefore reproduces the decode kernel's accumulation order
# bit-for-bit.


def _paged_verify_kernel(
    table_ref, lens_ref, start_ref,  # scalar prefetch (SMEM)
    q_ref, k_ref, v_ref,            # blocks picked by index maps
    o_ref,
    m_ref, l_ref, acc_ref,          # VMEM scratch
    *, block_len: int, group: int, qlen: int, window: Optional[int],
):
    b = pl.program_id(0)
    i = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[b]
    row0 = start_ref[b] + i * block_len
    rows = group * qlen

    @pl.when(row0 < length + qlen - 1)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)    # [group·Q, D] (pre-scaled)
        k = k_ref[0, 0].astype(jnp.float32)    # [block_len, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [group·Q, block_len]
        pos = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_len), 1)
        # flash row r is query position r % Q of query-head group r // Q
        eff = length + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_len), 0) % qlen
        mask = pos < eff
        if window is not None:
            mask &= pos >= eff - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                    # [group·Q, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def _finish():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret"))
def paged_attention_verify_pallas(
    q: jax.Array,            # [B, Hq, Q, D] float (post-RoPE)
    k_pool: jax.Array,       # [N, Hkv, block_len, D]
    v_pool: jax.Array,       # [N, Hkv, block_len, D]
    block_table: jax.Array,  # [B, M] int32
    lens: jax.Array,         # [B] int32: committed_len + 1
    *,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    b, hq, qlen, d = q.shape
    n, hkv, blk, _ = k_pool.shape
    m = block_table.shape[1]
    group = hq // hkv
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    rows = group * qlen
    # [B, Hq, Q, D] → [B, Hkv, group·Q, D]: row (g, j), query index fastest
    qg = (q.astype(jnp.float32) * (d ** -0.5)).reshape(b, hkv, rows, d)
    if start is None:
        start = jnp.zeros((b,), jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, m),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda bi, h, i, tbl, ln, st: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, blk, d),
                         lambda bi, h, i, tbl, ln, st: (tbl[bi, i], h, 0, 0)),
            pl.BlockSpec((1, 1, blk, d),
                         lambda bi, h, i, tbl, ln, st: (tbl[bi, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, rows, d), lambda bi, h, i, tbl, ln, st: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_verify_kernel, block_len=blk, group=group, qlen=qlen,
        window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), jnp.asarray(lens, jnp.int32),
      jnp.asarray(start, jnp.int32), qg, k_pool, v_pool)
    return out.reshape(b, hq, qlen, d)


def _paged_verify_int8_kernel(
    table_ref, lens_ref, start_ref, ks_ref, vs_ref,  # scalar prefetch
    q_ref, k_ref, v_ref,
    o_ref,
    m_ref, l_ref, acc_ref,
    *, block_len: int, group: int, qlen: int, window: Optional[int],
    q_scale: float,
):
    b = pl.program_id(0)
    i = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[b]
    row0 = start_ref[b] + i * block_len
    blk_id = table_ref[b, i]
    rows = group * qlen

    @pl.when(row0 < length + qlen - 1)
    def _block():
        q8 = q_ref[0, 0]                       # [group·Q, D] int8
        k8 = k_ref[0, 0]                       # [block_len, D] int8
        v8 = v_ref[0, 0]
        s32 = jax.lax.dot_general(
            q8, k8, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
        s = s32.astype(jnp.float32) * (q_scale * ks_ref[blk_id])
        pos = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_len), 1)
        eff = length + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_len), 0) % qlen
        mask = pos < eff
        if window is not None:
            mask &= pos >= eff - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v8.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv * vs_ref[blk_id]
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def _finish():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "q_scale", "interpret"))
def paged_attention_verify_int8_pallas(
    q: jax.Array,            # [B, Hq, Q, D] float (post-RoPE)
    k_pool: jax.Array,       # [N, Hkv, block_len, D] int8
    v_pool: jax.Array,       # [N, Hkv, block_len, D] int8
    block_table: jax.Array,  # [B, M] int32
    lens: jax.Array,         # [B] int32: committed_len + 1
    k_scale: jax.Array,      # [N] f32 per-block scales
    v_scale: jax.Array,      # [N] f32
    *,
    q_scale: float,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    b, hq, qlen, d = q.shape
    n, hkv, blk, _ = k_pool.shape
    m = block_table.shape[1]
    group = hq // hkv
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    if k_pool.dtype != jnp.int8 or v_pool.dtype != jnp.int8:
        raise ValueError(
            f"int8 kernel needs int8 pools, got {k_pool.dtype}/{v_pool.dtype}")
    rows = group * qlen
    qs = q.astype(jnp.float32) * (d ** -0.5)
    q8 = jnp.clip(jnp.round(qs / q_scale), -127, 127).astype(jnp.int8)
    qg = q8.reshape(b, hkv, rows, d)
    if start is None:
        start = jnp.zeros((b,), jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, hkv, m),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda bi, h, i, tbl, ln, st, ks, vs: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, blk, d),
                         lambda bi, h, i, tbl, ln, st, ks, vs:
                         (tbl[bi, i], h, 0, 0)),
            pl.BlockSpec((1, 1, blk, d),
                         lambda bi, h, i, tbl, ln, st, ks, vs:
                         (tbl[bi, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, rows, d),
            lambda bi, h, i, tbl, ln, st, ks, vs: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_verify_int8_kernel, block_len=blk, group=group, qlen=qlen,
        window=window, q_scale=q_scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), jnp.asarray(lens, jnp.int32),
      jnp.asarray(start, jnp.int32), jnp.asarray(k_scale, jnp.float32),
      jnp.asarray(v_scale, jnp.float32), qg, k_pool, v_pool)
    return out.reshape(b, hq, qlen, d)
