"""Attention module: training, prefill and decode paths.

Three execution paths, all sharing shapes/semantics:

  * ``chunked_attention`` — training/prefill. Flash-style q-block streaming
    (lax.map over query chunks + remat) so the S×S score matrix is never
    materialized — the float-domain mirror of the TAC's on-the-fly softmax
    schedule. Supports causal, bidirectional and sliding-window masks and
    GQA head grouping.
  * ``decode_attention`` — single-token decode against a (possibly ring-
    buffered) KV cache.
  * ``int8 path`` — the paper-faithful serving path through
    ``repro.kernels.ita_attention`` (used by the serving engine and the
    INT8 benchmarks; quantizes q/k/v post-RoPE, as calibrated static
    scales — see DESIGN.md §2).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

import os

from repro.kernels.ita_attention.ops import ita_attention

NEG_INF = -1e30
# §Perf baseline switch: REPRO_BASELINE_ATTN=1 restores the head-expanding
# GQA decode path for before/after roofline measurements.
_BASELINE_ATTN = os.environ.get("REPRO_BASELINE_ATTN") == "1"

# Static calibration scales for the INT8 serving path (cover ±4σ for unit-
# variance activations; a real deployment would calibrate per layer — the
# paper's flow likewise uses offline static quantization [9]).
ACT_SCALE = 4.0 / 127
KV_SCALE = 4.0 / 127
Q_SCALE = 4.0 / 127
ATTN_OUT_SCALE = 4.0 / 127
LOGIT_AMAX = 10.0


def _expand_kv(k: jax.Array, group: int) -> jax.Array:
    if group == 1:
        return k
    return jnp.repeat(k, group, axis=1)


def chunked_attention(
    q: jax.Array,  # [B, Hq, Sq, D]
    k: jax.Array,  # [B, Hkv, Skv, D]
    v: jax.Array,  # [B, Hkv, Skv, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,  # sliding window (tokens), None = global
    chunk_q: int = 128,
    q_offset: int = 0,  # global position of q[0] (prefill continuation)
) -> jax.Array:
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    k = _expand_kv(k, group)
    v = _expand_kv(v, group)
    scale = d ** -0.5
    bq = min(chunk_q, sq)
    sq_orig = sq
    if sq % bq:  # pad query length up to a chunk multiple (rows discarded)
        pad = bq - sq % bq
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        sq += pad
    nq = sq // bq

    cols = jnp.arange(skv)

    @jax.checkpoint
    def block(args):
        q_blk, row0 = args  # [B, H, bq, D], scalar
        rows = row0 + jnp.arange(bq) + q_offset
        # bf16 operands on the MXU, f32 accumulation (flash convention)
        logits = jnp.einsum(
            "bhqd,bhkd->bhqk", q_blk, k,
            preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((bq, skv), bool)
        if causal:
            mask &= cols[None, :] <= rows[:, None]
        if window is not None:
            mask &= cols[None, :] > rows[:, None] - window
        logits = jnp.where(mask, logits, NEG_INF)
        p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                          preferred_element_type=jnp.float32)

    q_blocks = q.reshape(b, hq, nq, bq, d).transpose(2, 0, 1, 3, 4)
    row0s = jnp.arange(nq) * bq
    out = jax.lax.map(block, (q_blocks, row0s))  # [nq, B, H, bq, D]
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, hq, sq, d)
    return out[:, :, :sq_orig].astype(q.dtype)


def decode_attention(
    q: jax.Array,        # [B, Hq, 1, D]
    k_cache: jax.Array,  # [B, Hkv, S_cache, D]
    v_cache: jax.Array,
    cache_len: jax.Array,  # [] or [B] int32 — valid entries (per row)
    *,
    ring: bool = False,    # ring buffer (sliding-window cache)
    expand_kv: bool = None,  # baseline (pre-§Perf) head-materializing path
) -> jax.Array:
    if expand_kv is None:
        expand_kv = _BASELINE_ATTN
    b, hq, _, d = q.shape
    _, hkv, s_cache, _ = k_cache.shape
    group = hq // hkv
    idx = jnp.arange(s_cache)
    # cache_len broadcasts: scalar (uniform) or [B] (per-slot positions)
    cl = jnp.asarray(cache_len, jnp.int32).reshape(-1, 1)
    if ring:
        cl = jnp.minimum(cl, s_cache)
    valid = idx[None, :] < cl  # [B or 1, S]
    if expand_kv:
        k = _expand_kv(k_cache, group)
        v = _expand_kv(v_cache, group)
        logits = jnp.einsum(
            "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
        ) * (d ** -0.5)
        logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
        return out.astype(q.dtype)
    # §Perf grouped path: KV heads stay unexpanded — the dot carries the
    # query-group dim instead of repeating KV (16× less cache traffic for
    # glm4's kv=2/q=32) — see EXPERIMENTS.md §Perf iteration 1.
    qg = q.reshape(b, hkv, group, d)  # sq==1 folded into group rows
    logits = jnp.einsum(
        "bhgd,bhkd->bhgk", qg.astype(jnp.float32),
        k_cache.astype(jnp.float32)) * (d ** -0.5)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, hq, 1, d).astype(q.dtype)


def decode_attention_int8(
    q: jax.Array,         # [B, Hq, 1, D] float (post-RoPE)
    k_cache8: jax.Array,  # [B, Hkv, S_cache, D] int8 (scale KV_SCALE)
    v_cache8: jax.Array,
    cache_len: jax.Array,
    cfg,
    *,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,  # [B] abs position of cache row 0
) -> jax.Array:
    """One-token ITA integer attention against an int8 KV cache.

    Mirrors the ITA pipeline (int8 logits → base-2 integer softmax → int8
    probabilities into the AV accumulation) on a single query row. Storing
    the cache in int8 halves decode memory traffic — the dominant roofline
    term for decode cells (see EXPERIMENTS.md §Roofline).

    ``window`` masks entries before ``cache_len − window`` — needed by
    caches that store full-length history (the paged layout); ring caches
    enforce the window physically and leave it None. ``start`` shifts the
    masking to absolute positions for caches gathered from a rotating ring
    block table (row ``j`` holds absolute position ``start + j``).
    """
    from repro.core import ita

    b, hq, _, d = q.shape
    _, hkv, s_cache, _ = k_cache8.shape
    group = hq // hkv
    qs = q.astype(jnp.float32) * (d ** -0.5)
    q8 = jnp.clip(jnp.round(qs / Q_SCALE), -127, 127).astype(jnp.int8)
    if _BASELINE_ATTN:
        # pre-§Perf baseline: materialize the KV repeat (×group traffic)
        q8g = q8.reshape(b, hq, 1, d)
        k8 = _expand_kv(k_cache8, group)
        v8 = _expand_kv(v_cache8, group)
    else:
        # grouped GQA (§Perf iteration 1): no KV head expansion — the int8
        # cache is read once, not ×(Hq/Hkv)
        q8g = q8.reshape(b, hkv, group, d)
        k8, v8 = k_cache8, v_cache8

    s32 = jax.lax.dot_general(
        q8g, k8, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32,
    )  # [B, Hkv, group, S]
    from repro.core.quant import quantize_to_fixed_point_py, requantize

    s_logit = LOGIT_AMAX / 127.0
    mlt, sh = quantize_to_fixed_point_py(Q_SCALE * KV_SCALE / s_logit)
    s8 = requantize(s32, jnp.int32(mlt), jnp.int32(sh))
    spec = ita.SoftmaxSpec(s_logit)
    t = (s8.astype(jnp.int32) * spec.alpha_mult) >> spec.alpha_rshift
    neg = -(31 << ita.FB)
    t = jnp.maximum(t, neg)
    idx = jnp.arange(s_cache)[None, None, None, :]
    if start is not None:
        idx = idx + jnp.asarray(start, jnp.int32).reshape(-1, 1, 1, 1)
    # cache_len: scalar or per-row [B] position vector
    cl = jnp.asarray(cache_len, jnp.int32).reshape(-1, 1, 1, 1)
    valid = idx < cl
    if window is not None:
        valid &= idx >= cl - window
    t = jnp.where(valid, t, neg)
    m = jnp.max(t, -1, keepdims=True)
    be = -((-m) >> ita.FB)
    e = ita.exp2_fixed(jnp.maximum(t - (be << ita.FB), neg))
    p8 = jnp.minimum(e >> 1, 127).astype(jnp.int8)
    av = jax.lax.dot_general(
        p8, v8, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32,
    )  # [B, Hkv, group, D]
    den = jnp.maximum(jnp.sum(p8.astype(jnp.int32), -1, keepdims=True), 1)
    y = av.astype(jnp.float32) / den.astype(jnp.float32) * KV_SCALE
    return y.reshape(b, hq, 1, d).astype(q.dtype)


def int8_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    causal: bool = True,
    q_scale: float = 4.0 / 127,   # static calibration: post-RoPE/rsqrt(d) q
    k_scale: float = 4.0 / 127,
    v_scale: float = 4.0 / 127,
    out_scale: float = 4.0 / 127,
    backend: Optional[str] = None,
) -> jax.Array:
    """Paper-faithful INT8 attention (float in/out; quantized inside).

    Inputs are float [B, H, S, D] *after* RoPE; q is pre-scaled by 1/√d.
    Static scales come from calibration (defaults cover ±4σ activations).
    """
    d = q.shape[-1]
    qs = q.astype(jnp.float32) * (d ** -0.5)
    q8 = jnp.clip(jnp.round(qs / q_scale), -127, 127).astype(jnp.int8)
    k8 = jnp.clip(jnp.round(k.astype(jnp.float32) / k_scale), -127, 127).astype(jnp.int8)
    v8 = jnp.clip(jnp.round(v.astype(jnp.float32) / v_scale), -127, 127).astype(jnp.int8)
    y8 = ita_attention(
        q8, k8, v8, qk_scale=q_scale * k_scale, v_scale=v_scale,
        out_scale=out_scale, causal=causal, backend=backend,
    )
    return (y8.astype(jnp.float32) * out_scale).astype(q.dtype)
