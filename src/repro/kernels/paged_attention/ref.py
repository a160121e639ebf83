"""Dense oracles for paged decode attention (float and int8 pools).

Gathers each row's KV blocks from the shared pool into a contiguous
``[B, Hkv, max_blocks·block_len, D]`` view (block-table order IS position
order — position ``p`` lives in table entry ``(p - start) // block_len``
at offset ``p % block_len``; ``start`` is 0 for full-history tables and
the first live block's absolute position for sliding-window ring tables)
and runs the standard masked decode attention over it.

This is also the ``xla`` serving backend on CPU: the gather is one
``take`` per layer and XLA fuses the rest; entries past ``lens`` (and, for
sliding-window layers, before ``lens - window``) are masked to −∞, so the
result is bit-identical to decoding against a dense per-slot arena holding
the same values (softmax of −∞ rows contributes exact zeros).

Int8 pools get two oracles with different contracts:

  * ``paged_attention_int8_ref`` — gather + the ITA integer pipeline
    (``models.attention.decode_attention_int8``). Integer arithmetic over
    int8 blocks is exact, so this is *bit-identical* to the dense int8
    serving reference (which decodes the same requantized values from its
    per-slot arena). It is the ``xla`` serving backend for quantized archs
    and assumes the static ``KV_SCALE`` calibration.
  * ``paged_attention_int8_dequant_ref`` — gather + on-the-fly dequant
    (honoring per-block scales) + f32 softmax over int8 q·k logits: the
    numerical contract of the fused Pallas kernel, which streams blocks
    and cannot run the ITA softmax's global integer max. The two oracles
    agree to integer-softmax quantization error (~1%), not bit-exactly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _valid_mask(s: int, lens, window, start):
    """[B, S] absolute-position validity mask shared by every oracle:
    gathered entry ``j`` holds absolute position ``start + j`` (``start``
    None ⇒ 0), valid iff inside ``[lens - window, lens)``."""
    idx = jnp.arange(s)[None, :]
    if start is not None:
        idx = idx + jnp.asarray(start, jnp.int32).reshape(-1, 1)
    cl = jnp.asarray(lens, jnp.int32).reshape(-1, 1)
    valid = idx < cl
    if window is not None:
        valid &= idx >= cl - window
    return valid


def _verify_mask(s: int, qlen: int, lens, window, start):
    """[B, Q, S] per-query validity mask for the small-q verify step.

    Query row ``j`` sits ``j`` positions past the committed frontier, so
    its effective length is ``lens + j`` — row 0 sees exactly what a
    plain decode step sees (``lens`` keys), row ``j`` additionally sees
    the ``j`` draft positions written before it this dispatch."""
    idx = jnp.arange(s)[None, None, :]
    if start is not None:
        idx = idx + jnp.asarray(start, jnp.int32).reshape(-1, 1, 1)
    cl = (jnp.asarray(lens, jnp.int32).reshape(-1, 1, 1)
          + jnp.arange(qlen, dtype=jnp.int32)[None, :, None])
    valid = idx < cl
    if window is not None:
        valid &= idx >= cl - window
    return valid


def gather_kv(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """[N, Hkv, blk, D] pool + [B, M] table → [B, Hkv, M·blk, D] dense KV."""
    n, hkv, blk, d = pool.shape
    b, m = block_table.shape
    g = pool[block_table]                # [B, M, Hkv, blk, D]
    return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, m * blk, d)


def paged_attention_ref(
    q: jax.Array,            # [B, Hq, 1, D] float
    k_pool: jax.Array,       # [N, Hkv, blk, D]
    v_pool: jax.Array,       # [N, Hkv, blk, D]
    block_table: jax.Array,  # [B, M] int32 pool indices
    lens: jax.Array,         # [B] int32 valid positions per row
    *,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,  # [B] int32 abs position of entry 0
) -> jax.Array:
    b, hq, _, d = q.shape
    _, hkv, blk, _ = k_pool.shape
    group = hq // hkv
    k = gather_kv(k_pool, block_table)   # [B, Hkv, S, D]
    v = gather_kv(v_pool, block_table)
    s = k.shape[2]
    valid = _valid_mask(s, lens, window, start)
    # grouped GQA (no KV head expansion), f32 softmax — matches
    # models.attention.decode_attention numerics exactly
    qg = q.reshape(b, hkv, group, d)
    logits = jnp.einsum(
        "bhgd,bhkd->bhgk", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * (d ** -0.5)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    # rows with no valid entries (empty serve slots) produce zeros, not the
    # uniform average a softmax over all-(−∞) logits would give — for any
    # row with ≥1 valid entry this mask is an exact no-op (those probs are
    # already exactly 0), so dense-arena token identity is unaffected
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, 1, d).astype(q.dtype)


def paged_attention_int8_ref(
    q: jax.Array,            # [B, Hq, 1, D] float (post-RoPE)
    k_pool: jax.Array,       # [N, Hkv, blk, D] int8 (KV_SCALE calibration)
    v_pool: jax.Array,       # [N, Hkv, blk, D] int8
    block_table: jax.Array,  # [B, M] int32
    lens: jax.Array,         # [B] int32
    *,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,
) -> jax.Array:
    """ITA gather oracle: the ``xla`` backend for int8 block pools.

    Gathers the int8 blocks densely and runs the exact ITA integer
    pipeline (int8 logits → base-2 integer softmax → int8 probabilities),
    so the result is bit-identical to the dense int8 serving path decoding
    the same requantized values — the anchor of the int8 paged-vs-dense
    token-identity matrix. Assumes the static ``attn.KV_SCALE``
    calibration (per-block scale pools exist for the fused kernel; this
    oracle's fixed-point requant constants are compiled from the static
    scale).
    """
    # lazy import: models.attention imports kernels.ita_attention; pulling
    # it at module scope would couple the kernel package import order
    from repro.models.attention import decode_attention_int8

    k = gather_kv(k_pool, block_table)
    v = gather_kv(v_pool, block_table)
    return decode_attention_int8(q, k, v, lens, None, window=window,
                                 start=start)


def paged_attention_int8_dequant_ref(
    q: jax.Array,            # [B, Hq, 1, D] float (post-RoPE)
    k_pool: jax.Array,       # [N, Hkv, blk, D] int8
    v_pool: jax.Array,       # [N, Hkv, blk, D] int8
    block_table: jax.Array,  # [B, M] int32
    lens: jax.Array,         # [B] int32
    *,
    k_scale,                 # python float or per-block [N] f32
    v_scale,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,
) -> jax.Array:
    """Dequant oracle: the fused int8 kernel's numerical contract.

    Same quantized operands as the kernel — q is requantized with the
    static ``Q_SCALE``, logits are exact int8·int8 dot products dequantized
    with the (possibly per-block) K scale — but softmax and the AV
    accumulation run in f32, densely. The kernel must match this to flash
    reordering error only.
    """
    from repro.models.attention import Q_SCALE

    b, hq, _, d = q.shape
    _, hkv, blk, _ = k_pool.shape
    group = hq // hkv
    k8 = gather_kv(k_pool, block_table)  # [B, Hkv, S, D] int8
    v8 = gather_kv(v_pool, block_table)
    s = k8.shape[2]

    def entry_scale(scale):
        """Per gathered entry [B, 1, 1, S] f32 (block scale repeated)."""
        scale = jnp.asarray(scale, jnp.float32)
        if scale.ndim == 0:
            return scale
        per_block = scale[block_table]                 # [B, M]
        return jnp.repeat(per_block, blk, axis=1)[:, None, None, :]

    qs = q.astype(jnp.float32) * (d ** -0.5)
    q8 = jnp.clip(jnp.round(qs / Q_SCALE), -127, 127)
    qg = q8.reshape(b, hkv, group, d)
    s32 = jnp.einsum("bhgd,bhkd->bhgk", qg, k8.astype(jnp.float32))
    logits = s32 * Q_SCALE * entry_scale(k_scale)
    valid = _valid_mask(s, lens, window, start)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    # fold the per-entry V scale into the probabilities (scale is per key
    # entry, so p·(scale·v) == (p·scale)·v) — one broadcast either way
    out = jnp.einsum("bhgk,bhkd->bhgd", p * entry_scale(v_scale),
                     v8.astype(jnp.float32))
    return out.reshape(b, hq, 1, d).astype(q.dtype)


def paged_attention_verify_ref(
    q: jax.Array,            # [B, Hq, Q, D] float — Q = spec_tokens + 1
    k_pool: jax.Array,       # [N, Hkv, blk, D]
    v_pool: jax.Array,       # [N, Hkv, blk, D]
    block_table: jax.Array,  # [B, M] int32 pool indices
    lens: jax.Array,         # [B] int32: committed_len + 1 (row 0's length)
    *,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,
) -> jax.Array:
    """Small-q verify oracle: the speculative-decode reference backend.

    Query row ``j`` scores draft position ``committed + j`` and attends
    ``lens + j`` keys (the committed history plus the ``j`` drafts written
    before it). Row 0 is exactly a decode step, so with all drafts
    rejected the verify step degenerates to ``paged_attention_ref`` —
    token identity with the non-speculative engine falls out of that.
    """
    b, hq, qlen, d = q.shape
    _, hkv, blk, _ = k_pool.shape
    group = hq // hkv
    k = gather_kv(k_pool, block_table)   # [B, Hkv, S, D]
    v = gather_kv(v_pool, block_table)
    s = k.shape[2]
    valid = _verify_mask(s, qlen, lens, window, start)    # [B, Q, S]
    qg = q.reshape(b, hkv, group, qlen, d)
    logits = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * (d ** -0.5)
    logits = jnp.where(valid[:, None, None, :, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(valid[:, None, None, :, :], p, 0.0)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, qlen, d).astype(q.dtype)


def paged_attention_verify_int8_ref(
    q: jax.Array,            # [B, Hq, Q, D] float (post-RoPE)
    k_pool: jax.Array,       # [N, Hkv, blk, D] int8 (KV_SCALE calibration)
    v_pool: jax.Array,       # [N, Hkv, blk, D] int8
    block_table: jax.Array,  # [B, M] int32
    lens: jax.Array,         # [B] int32: committed_len + 1
    *,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,
) -> jax.Array:
    """Multi-q ITA gather oracle: the ``xla`` verify backend for int8 pools.

    The ITA pipeline is exact integer arithmetic per query row (int32 max
    and sums have no reduction-order error), so each row here is
    *bit-identical* to ``paged_attention_int8_ref`` run at that row's
    effective length — the anchor of the spec-on/off int8 identity matrix.
    """
    from repro.core import ita
    from repro.core.quant import quantize_to_fixed_point_py, requantize
    from repro.models.attention import KV_SCALE, LOGIT_AMAX, Q_SCALE

    b, hq, qlen, d = q.shape
    _, hkv, blk, _ = k_pool.shape
    group = hq // hkv
    k8 = gather_kv(k_pool, block_table)  # [B, Hkv, S, D] int8
    v8 = gather_kv(v_pool, block_table)
    s = k8.shape[2]

    qs = q.astype(jnp.float32) * (d ** -0.5)
    q8 = jnp.clip(jnp.round(qs / Q_SCALE), -127, 127).astype(jnp.int8)
    # fold Q into the grouped-row axis: row r of kv-head h is (g, j) with
    # the query index j fastest, matching the [B, Q, S] mask broadcast
    qg = q8.reshape(b, hkv, group * qlen, d)
    s32 = jax.lax.dot_general(
        qg, k8, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32)          # [B, Hkv, G·Q, S]
    s_logit = LOGIT_AMAX / 127.0
    mlt, sh = quantize_to_fixed_point_py(Q_SCALE * KV_SCALE / s_logit)
    s8 = requantize(s32, jnp.int32(mlt), jnp.int32(sh))
    spec = ita.SoftmaxSpec(s_logit)
    t = (s8.astype(jnp.int32) * spec.alpha_mult) >> spec.alpha_rshift
    neg = -(31 << ita.FB)
    t = jnp.maximum(t, neg)
    valid = _verify_mask(s, qlen, lens, window, start)    # [B, Q, S]
    validr = jnp.broadcast_to(
        valid[:, None, None, :, :], (b, 1, group, qlen, s)
    ).reshape(b, 1, group * qlen, s)
    t = jnp.where(validr, t, neg)
    m = jnp.max(t, axis=-1, keepdims=True)
    be = -((-m) >> ita.FB)
    e = ita.exp2_fixed(jnp.maximum(t - (be << ita.FB), neg))
    p8 = jnp.minimum(e >> 1, 127).astype(jnp.int8)
    av = jax.lax.dot_general(
        p8, v8, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32)          # [B, Hkv, G·Q, D]
    den = jnp.maximum(jnp.sum(p8.astype(jnp.int32), axis=-1,
                              keepdims=True), 1)
    y = av.astype(jnp.float32) / den.astype(jnp.float32) * KV_SCALE
    return y.reshape(b, hkv, group, qlen, d).reshape(
        b, hq, qlen, d).astype(q.dtype)


def paged_attention_verify_int8_dequant_ref(
    q: jax.Array,            # [B, Hq, Q, D] float (post-RoPE)
    k_pool: jax.Array,       # [N, Hkv, blk, D] int8
    v_pool: jax.Array,       # [N, Hkv, blk, D] int8
    block_table: jax.Array,  # [B, M] int32
    lens: jax.Array,         # [B] int32: committed_len + 1
    *,
    k_scale,                 # python float or per-block [N] f32
    v_scale,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,
) -> jax.Array:
    """Dequant verify oracle: the fused int8 verify kernel's contract
    (f32 softmax over dequantized int8·int8 logits, per-query masks)."""
    from repro.models.attention import Q_SCALE

    b, hq, qlen, d = q.shape
    _, hkv, blk, _ = k_pool.shape
    group = hq // hkv
    k8 = gather_kv(k_pool, block_table)
    v8 = gather_kv(v_pool, block_table)
    s = k8.shape[2]

    def entry_scale(scale):
        scale = jnp.asarray(scale, jnp.float32)
        if scale.ndim == 0:
            return scale
        per_block = scale[block_table]                 # [B, M]
        return jnp.repeat(per_block, blk,
                          axis=1)[:, None, None, None, :]

    qs = q.astype(jnp.float32) * (d ** -0.5)
    q8 = jnp.clip(jnp.round(qs / Q_SCALE), -127, 127)
    qg = q8.reshape(b, hkv, group, qlen, d)
    s32 = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k8.astype(jnp.float32))
    logits = s32 * Q_SCALE * entry_scale(k_scale)
    valid = _verify_mask(s, qlen, lens, window, start)
    logits = jnp.where(valid[:, None, None, :, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(valid[:, None, None, :, :], p, 0.0)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p * entry_scale(v_scale),
                     v8.astype(jnp.float32))
    return out.reshape(b, hq, qlen, d).astype(q.dtype)


def paged_attention_sharded_oracle(
    q: jax.Array,            # [B, Hq, 1, D] float
    k_pool: jax.Array,       # [N, Hkv, blk, D]
    v_pool: jax.Array,       # [N, Hkv, blk, D]
    block_table: jax.Array,  # [B, M] int32
    lens: jax.Array,         # [B] int32
    mesh,
    *,
    axis: str = "model",
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,
) -> jax.Array:
    """Head-sharded shard_map harness over ``paged_attention_ref``.

    Splits the KV-head axis of the pools (and the grouped query heads)
    over ``mesh[axis]``, runs the rank-local oracle on each shard, and
    reassembles the output on its head axis. Because decode attention is
    per-head independent, the result is *bit-identical* to the one-device
    oracle — this is the contract the mesh-sharded serving path's
    "heads" mode builds on, and what the sharded tests pin down.
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    nshard = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    hkv = k_pool.shape[1]
    if hkv % nshard:
        raise ValueError(
            f"KV heads ({hkv}) must divide the '{axis}' mesh axis "
            f"({nshard}) — block-shard the pool instead")

    def body(q, kp, vp, bt, ln, st):
        return paged_attention_ref(q, kp, vp, bt, ln, window=window,
                                   start=st)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis), P(), P(),
                  P()),
        out_specs=P(None, axis), check_vma=False)
    return fn(q, k_pool, v_pool, block_table, lens, start)
