"""Dense decoder-only transformer family (phi3 ×2, glm4, gemma3, llava-LM).

Execution structure: layers are grouped by the config's repeating pattern
(e.g. gemma3 ``"LLLLLG"`` = 5 local : 1 global) and the stack is evaluated
as ``lax.scan`` over groups — one group body in the HLO regardless of depth,
which keeps 14B-parameter graphs compilable on this container's single CPU
core and is the layout production frameworks use for fast compiles.

Parameters live in per-pattern-position stacks of shape [n_groups, ...];
tail layers (n_layers % period) are applied unscanned.

Serving: the INT8 path (``serve_quant=True``) runs the paper's technique —
W8A8 projections via ``kernels.int8_gemm``, KV cache stored int8 (static
scales), attention through the ITA integer pipeline. Norms, RoPE and the
LM head stay in float (see DESIGN.md §2 assumption 3).

INT8 is a *residency* property, not just a compute property: every serving
write path — prefill fill, dense-arena decode write, paged-pool prefill
and decode writes — requantizes K/V with ``cache.quantize_kv`` at write
time when ``serve_quant`` is set, so the dense reference engines and the
int8 block pool hold the *same* integers and paged-vs-dense decoding is
token-identical. Weight quantization (``qparams``) remains a separate
switch (the engines enable both together for this family).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.models import attention as attn
from repro.models import layers as nn
from repro.models.config import ModelConfig
from repro.models.schema import TensorSpec
from repro.parallel import context as pctx

# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------


def _layer_schema(cfg: ModelConfig, n_stack: int) -> Dict[str, TensorSpec]:
    d, hd = cfg.d_model, cfg.hd
    nq, nkv, f = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
    L = ("layers",)

    def t(shape, axes, **kw):
        return TensorSpec((n_stack, *shape), L + axes, **kw)

    return {
        "ln1": t((d,), ("embed",), init="zeros"),
        "wq": t((d, nq * hd), ("embed", "heads")),
        "wk": t((d, nkv * hd), ("embed", "kv")),
        "wv": t((d, nkv * hd), ("embed", "kv")),
        "wo": t((nq * hd, d), ("heads", "embed")),
        "ln2": t((d,), ("embed",), init="zeros"),
        "wg": t((d, f), ("embed", "mlp")),
        "wu": t((d, f), ("embed", "mlp")),
        "wd": t((f, d), ("mlp", "embed")),
    }


def schema(cfg: ModelConfig):
    pattern, n_groups, tail = cfg.layer_layout()
    s: Dict[str, Any] = {
        "embed": TensorSpec((cfg.vocab, cfg.d_model), ("vocab", "embed_io"),
                            init="embed"),
        "final_norm": TensorSpec((cfg.d_model,), ("embed",), init="zeros"),
        "stacks": [_layer_schema(cfg, n_groups) for _ in pattern],
    }
    if tail:
        s["tail"] = [_layer_schema(cfg, 1) for _ in tail]
    if not cfg.tie_embeddings:
        s["unembed"] = TensorSpec((cfg.vocab, cfg.d_model), ("vocab", "embed_io"))
    return s


# ---------------------------------------------------------------------------
# Float (training / prefill) path
# ---------------------------------------------------------------------------


def _project_qkv(x, p, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    hd = cfg.hd
    q = nn.dense(x, p["wq"]).reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = nn.dense(x, p["wk"]).reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = nn.dense(x, p["wv"]).reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    q = pctx.constrain(nn.rope(q, positions, cfg.rope_theta),
                       ("batch", "heads", None, None))
    k = pctx.constrain(nn.rope(k, positions, cfg.rope_theta),
                       ("batch", "kv", None, None))
    v = pctx.constrain(v, ("batch", "kv", None, None))
    return q, k, v


def _merge_heads(o):
    b, h, s, hd = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, s, h * hd)


def _mlp(x, p, cfg: ModelConfig):
    act = nn.ACTIVATIONS[cfg.act]
    h = act(nn.dense(x, p["wg"]), nn.dense(x, p["wu"]))
    return nn.dense(pctx.constrain(h, ("batch", None, "mlp")), p["wd"])


def _layer(x, p, kind: str, cfg: ModelConfig, positions):
    h = nn.rms_norm(x, p["ln1"])
    q, k, v = _project_qkv(h, p, cfg, positions)
    o = attn.chunked_attention(
        q, k, v,
        causal=kind != "B",
        window=cfg.local_window if kind == "L" else None,
        chunk_q=min(cfg.attn_chunk_q, x.shape[1]),
    )
    x = x + nn.dense(_merge_heads(o), p["wo"])
    x = x + _mlp(nn.rms_norm(x, p["ln2"]), p, cfg)
    return pctx.constrain(x, ("batch", None, None))


def forward(params, tokens, cfg: ModelConfig, *, embeds=None):
    """Teacher-forcing logits [B, S, V]. ``embeds`` overrides token embedding
    (vlm/audio frontend stubs)."""
    pattern, n_groups, tail = cfg.layer_layout()
    x = embeds if embeds is not None else nn.embed(
        tokens, params["embed"], cfg.compute_dtype)
    x = pctx.constrain(x, ("batch", None, None))
    positions = jnp.arange(x.shape[1])

    def apply_group(xc, stacks_slice):
        for kind, p in zip(pattern, stacks_slice):
            xc = _layer(xc, p, kind, cfg, positions)
        return xc

    if cfg.remat:  # save only per-group carries; recompute internals in bwd
        apply_group = jax.checkpoint(apply_group)

    def group_body(xc, stacks_slice):
        return apply_group(xc, stacks_slice), None

    if n_groups > 0:
        x, _ = jax.lax.scan(group_body, x, tuple(params["stacks"]))
    for kind, p in zip(tail, params.get("tail", [])):
        x = _layer(x, jax.tree.map(lambda a: a[0], p), kind, cfg, positions)

    x = nn.rms_norm(x, params["final_norm"])
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return nn.unembed(x, table)


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------


def _cache_len_for(kind: str, cfg: ModelConfig, max_len: int) -> int:
    return min(cfg.local_window, max_len) if kind == "L" else max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               quantized: Optional[bool] = None):
    """Abstract-able KV cache pytree (stacked per pattern position)."""
    if quantized is None:
        quantized = cfg.serve_quant
    dt = jnp.int8 if quantized else cfg.compute_dtype
    pattern, n_groups, tail = cfg.layer_layout()
    hd, nkv = cfg.hd, cfg.n_kv_heads

    def kv(n_stack, kind):
        s_len = _cache_len_for(kind, cfg, max_len)
        return {
            "k": jnp.zeros((n_stack, batch, nkv, s_len, hd), dt),
            "v": jnp.zeros((n_stack, batch, nkv, s_len, hd), dt),
        }

    cache: Dict[str, Any] = {
        "stacks": [kv(n_groups, kind) for kind in pattern],
        "len": jnp.zeros((batch,), jnp.int32),
    }
    if tail:
        cache["tail"] = [kv(1, kind) for kind in tail]
    return cache


def _as_positions(pos, batch: int) -> jax.Array:
    """Normalize a scalar or [B] ``len`` entry to a per-row position vector."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (batch,))


def _cache_write(c, k_new, v_new, pos, kind, cfg):
    """Write one token's k/v at per-row position ``pos`` [B] (ring for
    local layers). Rows may sit at different positions — the continuous-
    batching case — so the write is a per-row scatter."""
    b, _, s_len, _ = c["k"].shape
    pos = _as_positions(pos, b)
    idx = pos % jnp.int32(s_len) if kind == "L" else jnp.minimum(pos, s_len - 1)
    rows = jnp.arange(b)
    k = c["k"].at[rows, :, idx].set(k_new[:, :, 0].astype(c["k"].dtype))
    v = c["v"].at[rows, :, idx].set(v_new[:, :, 0].astype(c["v"].dtype))
    return {"k": k, "v": v}


def _decode_layer(x, p, c, kind, cfg: ModelConfig, pos, *, qparams=None):
    """One-token decode through one layer; returns (x, updated cache)."""
    int8 = qparams is not None
    h = nn.rms_norm(x, p["ln1"])
    b = x.shape[0]
    hd = cfg.hd
    lin = functools.partial(_qlin, qparams) if int8 else (
        lambda name, y: nn.dense(y, p[name]))
    q = lin("wq", h).reshape(b, 1, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = lin("wk", h).reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = lin("wv", h).reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    q = nn.rope(q, pos[:, None, None], cfg.rope_theta)  # per-row positions
    k = nn.rope(k, pos[:, None, None], cfg.rope_theta)

    if cfg.serve_quant:
        from repro.models.cache import quantize_kv

        c = _cache_write(c, quantize_kv(k, attn.KV_SCALE),
                         quantize_kv(v, attn.KV_SCALE), pos, kind, cfg)
        o = attn.decode_attention_int8(q, c["k"], c["v"], pos + 1, cfg)
    else:
        c = _cache_write(c, k, v, pos, kind, cfg)
        o = attn.decode_attention(
            q, c["k"], c["v"], pos + 1, ring=kind == "L")
    x = x + lin("wo", _merge_heads(o))
    h = nn.rms_norm(x, p["ln2"])
    act = nn.ACTIVATIONS[cfg.act]
    x = x + lin("wd", act(lin("wg", h), lin("wu", h)))
    return x, c


def _qlin(qp_slice, name, y):
    """Quantized linear for the int8 serving path (static activation scale;
    the GEMM backend comes from the platform)."""
    from repro.kernels.int8_gemm.ops import int8_gemm

    s_in = attn.ACT_SCALE
    y8 = jnp.clip(jnp.round(y.astype(jnp.float32) / s_in), -127, 127).astype(jnp.int8)
    out8 = int8_gemm(y8, qp_slice[name])
    return (out8.astype(jnp.float32) * attn.ACT_SCALE).astype(y.dtype)


def decode_step(params, cache, tokens, cfg: ModelConfig, *, qparams=None,
                embeds=None):
    """One decode step. tokens [B] (or embeds [B, 1, D]); returns (logits, cache).

    ``cache["len"]`` is a per-row position vector [B] (a scalar is accepted
    for backward compatibility and broadcast), so rows of the batch can sit
    at different sequence positions — the continuous-batching serve layout.
    """
    pattern, n_groups, tail = cfg.layer_layout()
    x = embeds if embeds is not None else nn.embed(
        tokens[:, None], params["embed"], cfg.compute_dtype)
    pos = _as_positions(cache["len"], x.shape[0])

    def group_body(xc, slices):
        stacks_slice, cache_slice, q_slice = slices
        new_caches = []
        for i, kind in enumerate(pattern):
            xc, c = _decode_layer(
                xc, stacks_slice[i], cache_slice[i], kind, cfg, pos,
                qparams=None if q_slice is None else q_slice[i],
            )
            new_caches.append(c)
        return xc, tuple(new_caches)

    if n_groups > 0:
        qstacks = None if qparams is None else tuple(qparams["stacks"])
        x, new_stack_caches = jax.lax.scan(
            group_body, x,
            (tuple(params["stacks"]), tuple(cache["stacks"]),
             qstacks),
        )
        cache = dict(cache, stacks=list(new_stack_caches))
    for i, kind in enumerate(tail):
        p = jax.tree.map(lambda a: a[0], params["tail"][i])
        qp = None
        if qparams is not None:
            qp = jax.tree.map(lambda a: a[0], qparams["tail"][i])
        c_in = jax.tree.map(lambda a: a[0], cache["tail"][i])
        x, c = _decode_layer(x, p, c_in, kind, cfg, pos, qparams=qp)
        cache["tail"][i] = jax.tree.map(lambda a: a[None], c)

    x = nn.rms_norm(x, params["final_norm"])
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = nn.unembed(x, table)
    cache = dict(cache, len=cache["len"] + 1)
    return logits[:, 0], cache


# ---------------------------------------------------------------------------
# Paged KV cache + decode (block-pool serving layout)
# ---------------------------------------------------------------------------


def init_paged_cache(cfg: ModelConfig, slots: int, layout, *,
                     quantized: Optional[bool] = None):
    """Block-pool KV cache: per pattern-position stacks of shape
    ``[n_stack, num_blocks, Hkv, block_len, hd]`` shared by all ``slots``
    decode rows, plus the per-row position vector. The per-row block table
    that maps positions to pool blocks lives host-side (the serve engine
    owns it) and is passed into ``paged_decode_step`` each call.

    When ``layout.window`` is set, sliding-window ("L") stacks are sized
    ``layout.ring_num_blocks`` rows — each slot reuses a fixed ring of
    ``layout.ring_blocks`` blocks circularly, so per-sliding-layer pool
    residency is bounded by the window, not ``max_len``. With ``window``
    left ``None`` every layer stores full-length history and L layers are
    handled by a window mask at attention time (the PR-2 layout).

    **Int8 blocks** (``quantized``, default ``cfg.serve_quant``): pools
    store K/V as int8 — half the resident bytes of a bf16 pool per token —
    plus per-block scale vectors ``kscale``/``vscale`` ([n_stack,
    n_blocks] f32, filled with the static ``attn.KV_SCALE`` calibration;
    the arrays let per-block calibration land without a layout change).
    Every write path requantizes with ``cache.quantize_kv`` before
    storing, so pool contents are the same integers the dense int8
    reference holds in its float arena.
    """
    if quantized is None:
        quantized = cfg.serve_quant
    pattern, n_groups, tail = cfg.layer_layout()
    hd, nkv = cfg.hd, cfg.n_kv_heads
    dt = jnp.int8 if quantized else cfg.compute_dtype
    ring = getattr(layout, "window", None) is not None

    def kv(n_stack, kind):
        n_blocks = (layout.ring_num_blocks if ring and kind == "L"
                    else layout.num_blocks)
        shape = (n_stack, n_blocks, nkv, layout.block_len, hd)
        c = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
        if quantized:
            # two distinct buffers: the engines donate the cache pytree and
            # aliasing k/v scales would donate one buffer twice
            c["kscale"] = jnp.full((n_stack, n_blocks), attn.KV_SCALE,
                                   jnp.float32)
            c["vscale"] = jnp.full((n_stack, n_blocks), attn.KV_SCALE,
                                   jnp.float32)
        return c

    cache: Dict[str, Any] = {
        "stacks": [kv(n_groups, kind) for kind in pattern],
        "len": jnp.zeros((slots,), jnp.int32),
    }
    if tail:
        cache["tail"] = [kv(1, kind) for kind in tail]
    return cache


def _resolve_paged_table(table, kind: str):
    """(block table, start vector or None) for a layer of ``kind``.

    ``table`` is either a plain ``[slots, max_blocks]`` array (PR-2 layout:
    every layer walks the full-history table from position 0) or the ring
    dict ``{"full", "ring", "start"}`` the serve engine passes when
    sliding-window layers store ring blocks: L layers then walk the
    rotating ring table whose entry 0 sits at absolute position
    ``start[slot]``.
    """
    if isinstance(table, dict):
        if kind == "L":
            return table["ring"], table["start"]
        return table["full"], None
    return table, None


def _paged_cache_write(c, k_new, v_new, pos, table, block_len: int,
                       start=None):
    """Scatter one token's k/v at per-row position ``pos`` through the
    block table (ring tables pass ``start``, the absolute position of
    table entry 0). Empty rows point at the trash block (table row
    zeros), so their writes are harmless."""
    rows_b = pos.shape[0]
    max_blocks = table.shape[1]
    rel = pos if start is None else pos - jnp.asarray(start, jnp.int32)
    bi = jnp.clip(rel // jnp.int32(block_len), 0, max_blocks - 1)
    blk_ids = table[jnp.arange(rows_b), bi]        # [B] pool rows
    off = pos % jnp.int32(block_len)
    k = c["k"].at[blk_ids, :, off].set(k_new[:, :, 0].astype(c["k"].dtype))
    v = c["v"].at[blk_ids, :, off].set(v_new[:, :, 0].astype(c["v"].dtype))
    # dict(c, ...) keeps the int8 layout's per-block scale pools riding
    # along (static calibration: writes never touch them)
    return dict(c, k=k, v=v)


def _paged_decode_layer(x, p, c, kind, cfg: ModelConfig, pos, table, *,
                        qparams=None, attn_backend: Optional[str] = None,
                        shard=None):
    """One-token decode through one layer against the paged pool.

    Int8 block pools (``c["k"].dtype == int8``) take the fused quantized
    path: requantized K/V written straight into int8 blocks and
    ``paged_attention_int8`` over the pool — no dense gather, no float
    copy of the history. The ``xla`` backend of that op is the ITA gather
    oracle, bit-identical to the dense int8 reference.

    ``shard`` (``cache.KVShard``, inside a shard_map'd step): heads mode
    slices Q/K/V to the rank-local heads before the write/attend and
    all-gathers the attention output; blocks mode attends the rank-local
    block table and keeps owner rows via a masked psum. Either way the
    attention op itself stays rank-local.
    """
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ops import paged_attention_int8
    from repro.models.cache import (
        kv_shard_allgather, kv_shard_owner_rows, kv_shard_slice, quantize_kv,
    )

    int8_w = qparams is not None
    int8_kv = c["k"].dtype == jnp.int8
    if int8_w and not int8_kv:
        raise ValueError(
            "int8 serving over float block pools was removed (the dense-"
            "gather ITA detour): build the paged cache with quantized=True "
            "so K/V live in int8 blocks")
    h = nn.rms_norm(x, p["ln1"])
    b = x.shape[0]
    hd = cfg.hd
    block_len = c["k"].shape[2]  # [num_blocks, Hkv, block_len, hd]
    lin = functools.partial(_qlin, qparams) if int8_w else (
        lambda name, y: nn.dense(y, p[name]))
    q = lin("wq", h).reshape(b, 1, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = lin("wk", h).reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = lin("wv", h).reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    q = nn.rope(q, pos[:, None, None], cfg.rope_theta)
    k = nn.rope(k, pos[:, None, None], cfg.rope_theta)
    q, k, v = kv_shard_slice(shard, q, k, v)

    window = cfg.local_window if kind == "L" else None
    tbl, start = _resolve_paged_table(table, kind)
    if int8_kv:
        c = _paged_cache_write(c, quantize_kv(k, attn.KV_SCALE),
                               quantize_kv(v, attn.KV_SCALE), pos, tbl,
                               block_len, start=start)
        o = paged_attention_int8(q, c["k"], c["v"], tbl, pos + 1,
                                 k_scale=c["kscale"], v_scale=c["vscale"],
                                 window=window, start=start,
                                 backend=attn_backend)
    else:
        c = _paged_cache_write(c, k, v, pos, tbl, block_len, start=start)
        o = paged_attention(q, c["k"], c["v"], tbl, pos + 1,
                            window=window, start=start,
                            backend=attn_backend)
    o = kv_shard_allgather(shard, o)
    o = kv_shard_owner_rows(shard, o)
    x = x + lin("wo", _merge_heads(o))
    h = nn.rms_norm(x, p["ln2"])
    act = nn.ACTIVATIONS[cfg.act]
    x = x + lin("wd", act(lin("wg", h), lin("wu", h)))
    return x, c


def paged_decode_step(params, cache, tokens, cfg: ModelConfig, table, *,
                      qparams=None, embeds=None,
                      attn_backend: Optional[str] = None,
                      shard=None):
    """One decode step against the paged block pool.

    ``table`` [slots, max_blocks] int32 maps each row's position ``p`` to
    pool block ``table[row, p // block_len]`` (offset ``p % block_len``) —
    the engine allocates blocks host-side and passes the table each call
    (fixed shape, so the step never retraces). When sliding-window layers
    store ring blocks, ``table`` is instead the dict ``{"full": [slots,
    max_blocks], "ring": [slots, ring_blocks], "start": [slots]}`` (see
    ``_resolve_paged_table``). ``shard`` (``cache.KVShard``) threads the
    mesh-sharded pool view through every layer — see
    ``_paged_decode_layer``.
    """
    pattern, n_groups, tail = cfg.layer_layout()
    x = embeds if embeds is not None else nn.embed(
        tokens[:, None], params["embed"], cfg.compute_dtype)
    pos = _as_positions(cache["len"], x.shape[0])
    table = jax.tree.map(lambda a: jnp.asarray(a, jnp.int32), table)

    def group_body(xc, slices):
        stacks_slice, cache_slice, q_slice = slices
        new_caches = []
        for i, kind in enumerate(pattern):
            xc, c = _paged_decode_layer(
                xc, stacks_slice[i], cache_slice[i], kind, cfg, pos, table,
                qparams=None if q_slice is None else q_slice[i],
                attn_backend=attn_backend, shard=shard,
            )
            new_caches.append(c)
        return xc, tuple(new_caches)

    if n_groups > 0:
        qstacks = None if qparams is None else tuple(qparams["stacks"])
        x, new_stack_caches = jax.lax.scan(
            group_body, x,
            (tuple(params["stacks"]), tuple(cache["stacks"]), qstacks),
        )
        cache = dict(cache, stacks=list(new_stack_caches))
    for i, kind in enumerate(tail):
        p = jax.tree.map(lambda a: a[0], params["tail"][i])
        qp = None
        if qparams is not None:
            qp = jax.tree.map(lambda a: a[0], qparams["tail"][i])
        c_in = jax.tree.map(lambda a: a[0], cache["tail"][i])
        x, c = _paged_decode_layer(x, p, c_in, kind, cfg, pos, table,
                                   qparams=qp, attn_backend=attn_backend,
                                   shard=shard)
        cache["tail"][i] = jax.tree.map(lambda a: a[None], c)

    x = nn.rms_norm(x, params["final_norm"])
    table_w = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = nn.unembed(x, table_w)
    cache = dict(cache, len=cache["len"] + 1)
    return logits[:, 0], cache


def _paged_verify_write(c, k_new, v_new, pos, table, block_len: int,
                        start=None):
    """Scatter Q consecutive tokens' k/v per row: row ``b``'s query ``j``
    lands at position ``pos[b] + j`` through the block table. Rows whose
    trailing positions exceed their draft count write into the grown tail
    block's pad offsets (or, clipped, the trash block) — those positions
    are past every committed length, never attended, and always rewritten
    before the frontier reaches them."""
    rows_b = pos.shape[0]
    qlen = k_new.shape[2]                          # [B, Hkv, Q, hd]
    max_blocks = table.shape[1]
    positions = pos[:, None] + jnp.arange(qlen, dtype=jnp.int32)[None, :]
    rel = (positions if start is None
           else positions - jnp.asarray(start, jnp.int32)[:, None])
    bi_raw = rel // jnp.int32(block_len)
    bi = jnp.clip(bi_raw, 0, max_blocks - 1)
    blk_ids = table[jnp.arange(rows_b)[:, None], bi]    # [B, Q] pool rows
    # positions past the table (a full-length request's pad columns) must
    # divert to the trash block — clipping onto the last table entry
    # could overwrite a real block's committed offsets
    blk_ids = jnp.where(bi_raw >= max_blocks, jnp.int32(0), blk_ids)
    off = positions % jnp.int32(block_len)
    # advanced-index result axes lead: value shape [B, Q, Hkv, hd]
    k = c["k"].at[blk_ids, :, off].set(
        k_new.transpose(0, 2, 1, 3).astype(c["k"].dtype))
    v = c["v"].at[blk_ids, :, off].set(
        v_new.transpose(0, 2, 1, 3).astype(c["v"].dtype))
    return dict(c, k=k, v=v)


def _paged_verify_layer(x, p, c, kind, cfg: ModelConfig, pos, table, *,
                        qparams=None, attn_backend: Optional[str] = None):
    """Small-q (speculative verify) pass through one layer: ``x`` carries
    Q = spec_tokens + 1 positions per row — the last committed token plus
    the drafts — all written and scored in one pool sweep. Query row 0
    reproduces ``_paged_decode_layer``'s math bit-for-bit (same write, and
    the verify attention's row 0 is exactly the decode mask), which is
    what keeps greedy speculative serving token-identical."""
    from repro.kernels.paged_attention.ops import (
        paged_attention_verify, paged_attention_verify_int8,
    )
    from repro.models.cache import quantize_kv

    int8_w = qparams is not None
    int8_kv = c["k"].dtype == jnp.int8
    if int8_w and not int8_kv:
        raise ValueError(
            "int8 serving over float block pools was removed (the dense-"
            "gather ITA detour): build the paged cache with quantized=True "
            "so K/V live in int8 blocks")
    h = nn.rms_norm(x, p["ln1"])
    b, qlen = x.shape[:2]
    hd = cfg.hd
    block_len = c["k"].shape[2]
    lin = functools.partial(_qlin, qparams) if int8_w else (
        lambda name, y: nn.dense(y, p[name]))
    q = lin("wq", h).reshape(b, qlen, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = lin("wk", h).reshape(b, qlen, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = lin("wv", h).reshape(b, qlen, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    positions = pos[:, None] + jnp.arange(qlen, dtype=jnp.int32)[None, :]
    q = nn.rope(q, positions[:, None, :], cfg.rope_theta)
    k = nn.rope(k, positions[:, None, :], cfg.rope_theta)

    window = cfg.local_window if kind == "L" else None
    tbl, start = _resolve_paged_table(table, kind)
    if int8_kv:
        c = _paged_verify_write(c, quantize_kv(k, attn.KV_SCALE),
                                quantize_kv(v, attn.KV_SCALE), pos, tbl,
                                block_len, start=start)
        o = paged_attention_verify_int8(
            q, c["k"], c["v"], tbl, pos + 1,
            k_scale=c["kscale"], v_scale=c["vscale"],
            window=window, start=start, backend=attn_backend)
    else:
        c = _paged_verify_write(c, k, v, pos, tbl, block_len, start=start)
        o = paged_attention_verify(q, c["k"], c["v"], tbl, pos + 1,
                                   window=window, start=start,
                                   backend=attn_backend)
    x = x + lin("wo", _merge_heads(o))
    h = nn.rms_norm(x, p["ln2"])
    act = nn.ACTIVATIONS[cfg.act]
    x = x + lin("wd", act(lin("wg", h), lin("wu", h)))
    return x, c


def paged_verify_step(params, cache, tokens, cfg: ModelConfig, table, *,
                      qparams=None, attn_backend: Optional[str] = None):
    """Speculative-decode verify step: score Q = spec_tokens + 1 positions
    per slot in one dispatch against the paged pool.

    ``tokens`` [slots, Q] int32 — column 0 is each row's last committed
    token, columns 1.. are the host-drafted candidates (pad rows repeat
    anything; their logits are ignored host-side). Logits row ``j`` is the
    model's prediction *after* consuming ``tokens[:, :j+1]``, so the host
    commits the longest prefix where ``argmax(logits[j]) == tokens[j+1]``
    plus one bonus token.

    Unlike ``paged_decode_step`` the position vector is **host-owned**:
    ``cache["len"]`` is not advanced here — the engine commits the
    accepted count host-side and passes refreshed lengths next dispatch
    (draft K/V past the accept point stays in the pool as garbage that is
    never attended and always overwritten before the frontier reaches
    it; the allocator rolls the *blocks* back).

    Returns ``(logits [slots, Q, V], cache)``.
    """
    pattern, n_groups, tail = cfg.layer_layout()
    x = nn.embed(tokens, params["embed"], cfg.compute_dtype)  # [slots, Q, D]
    pos = _as_positions(cache["len"], x.shape[0])
    table = jax.tree.map(lambda a: jnp.asarray(a, jnp.int32), table)

    def group_body(xc, slices):
        stacks_slice, cache_slice, q_slice = slices
        new_caches = []
        for i, kind in enumerate(pattern):
            xc, c = _paged_verify_layer(
                xc, stacks_slice[i], cache_slice[i], kind, cfg, pos, table,
                qparams=None if q_slice is None else q_slice[i],
                attn_backend=attn_backend,
            )
            new_caches.append(c)
        return xc, tuple(new_caches)

    if n_groups > 0:
        qstacks = None if qparams is None else tuple(qparams["stacks"])
        x, new_stack_caches = jax.lax.scan(
            group_body, x,
            (tuple(params["stacks"]), tuple(cache["stacks"]), qstacks),
        )
        cache = dict(cache, stacks=list(new_stack_caches))
    for i, kind in enumerate(tail):
        p = jax.tree.map(lambda a: a[0], params["tail"][i])
        qp = None
        if qparams is not None:
            qp = jax.tree.map(lambda a: a[0], qparams["tail"][i])
        c_in = jax.tree.map(lambda a: a[0], cache["tail"][i])
        x, c = _paged_verify_layer(x, p, c_in, kind, cfg, pos, table,
                                   qparams=qp, attn_backend=attn_backend)
        cache["tail"][i] = jax.tree.map(lambda a: a[None], c)

    x = nn.rms_norm(x, params["final_norm"])
    table_w = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return nn.unembed(x, table_w), cache


def paged_insert(cache, single, slot, block_ids, cfg: ModelConfig):
    """Splice a batch-1 prefilled dense cache (sized to the admission
    bucket) into pool blocks ``block_ids`` and point ``slot``'s position
    counter at the prefill's true length."""
    from repro.models.cache import paged_insert_kv

    block_ids = jnp.asarray(block_ids, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)

    def splice(pool_kv, single_kv):
        # int8 pools: the single cache already holds requantized integers
        # (serve_quant prefill), so the astype inside paged_insert_kv is
        # exact; dict(...) keeps the scale pools
        return dict(pool_kv,
                    k=paged_insert_kv(pool_kv["k"], single_kv["k"],
                                      block_ids),
                    v=paged_insert_kv(pool_kv["v"], single_kv["v"],
                                      block_ids))

    out = dict(cache)
    out["stacks"] = [splice(pc, sc) for pc, sc
                     in zip(cache["stacks"], single["stacks"])]
    if "tail" in cache:
        out["tail"] = [splice(pc, sc) for pc, sc
                       in zip(cache["tail"], single["tail"])]
    new_len = jax.lax.dynamic_update_slice(
        cache["len"], single["len"].astype(jnp.int32), (slot,))
    out["len"] = new_len
    return out


def paged_prefill(params, tokens, cfg: ModelConfig, cache, slot, block_ids,
                  *, ring_ids=None, true_len=None, embeds=None,
                  prefix_ids=None, start=0, shard=None):
    """Prefill straight into pool blocks: forward pass + per-layer K/V
    writes into the paged ``cache`` — no intermediate dense bucket cache,
    no splice dispatch. Returns ``(last-position logits, updated cache)``.

    Full-history layers scatter all ``len(block_ids)`` blocks in bulk (the
    partially-valid tail block at block granularity); sliding-window ("L")
    layers write only the last ``len(ring_ids)`` blocks, circularly, under
    the ``bi % ring_blocks`` convention shared with the engine's rotating
    ring table (``ring_ids=None`` keeps every layer full-history — the
    PR-2 layout). ``true_len`` enables right-padded admission buckets
    exactly as in ``prefill``; ``slot``'s position counter is set to the
    true length. ``prefix_ids``/``start`` resume a prefix-cache hit:
    ``tokens`` carries only the uncached suffix and the cached blocks are
    attended, not recomputed (see ``_paged_prefill_impl``).
    """
    return _paged_prefill_impl(
        params, tokens, cfg, cache, slot, block_ids, layer_fn=_prefill_layer,
        ring_ids=ring_ids, true_len=true_len, embeds=embeds,
        prefix_ids=prefix_ids, start=start, shard=shard)


def _paged_prefill_impl(params, tokens, cfg: ModelConfig, cache, slot,
                        block_ids, *, layer_fn, ring_ids=None, true_len=None,
                        embeds=None, prefix_ids=None, start=0, shard=None):
    """Shared paged-prefill scaffold (block writes, scan over groups, tail
    layers, last-real-token logits, slot position update). ``layer_fn`` is
    the family's per-layer prefill application — the MoE family reuses
    this whole function with its expert-FFN layer.

    **Prefix-cache resume** (``prefix_ids``/``start``): the first ``start``
    positions of the sequence already live in pool blocks ``prefix_ids``
    (``start = len(prefix_ids) · block_len``, static). ``tokens`` then
    carries only the *suffix*; each layer gathers the cached prefix K/V
    from the pool, the suffix queries attend [prefix ++ suffix] at
    ``q_offset=start``, and only the suffix blocks (``block_ids``) are
    written. ``true_len`` stays the *total* true length. Ring layers
    cannot resume (the skipped prefix would leave their ring unwritten) —
    the backend disables prefix caching for ring layouts.

    Int8 block pools requantize K/V (``cache.quantize_kv``, static
    ``attn.KV_SCALE``) before the block write — the same write-time
    requantization the dense serving reference applies, so pool contents
    are bit-identical to what the dense arena holds."""
    from repro.models.cache import (
        gather_prefix_kv, kv_shard_prefix, prefill_write_kv, quantize_kv,
        ring_prefill_write_kv,
    )

    if prefix_ids is not None and ring_ids is not None:
        raise ValueError("prefix-cache resume is incompatible with ring "
                         "(sliding-window) prefill")
    pattern, n_groups, tail = cfg.layer_layout()
    x = embeds if embeds is not None else nn.embed(
        tokens, params["embed"], cfg.compute_dtype)
    b, s = x.shape[:2]
    start = int(start)
    positions = start + jnp.arange(s)
    block_ids = jnp.asarray(block_ids, jnp.int32)
    if ring_ids is not None:
        ring_ids = jnp.asarray(ring_ids, jnp.int32)
    if prefix_ids is not None:
        prefix_ids = jnp.asarray(prefix_ids, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    n = jnp.asarray(start + s if true_len is None else true_len, jnp.int32)

    def write(c_kv, k, v, kind):
        if c_kv["k"].dtype == jnp.int8:
            k = quantize_kv(k, attn.KV_SCALE)
            v = quantize_kv(v, attn.KV_SCALE)
        if kind == "L" and ring_ids is not None:
            return dict(c_kv,
                        k=ring_prefill_write_kv(c_kv["k"], k, ring_ids, n),
                        v=ring_prefill_write_kv(c_kv["v"], v, ring_ids, n))
        return dict(c_kv,
                    k=prefill_write_kv(c_kv["k"], k, block_ids),
                    v=prefill_write_kv(c_kv["v"], v, block_ids))

    def prefix_of(c_kv):
        """Cached-prefix K/V for one layer (gathered *before* the suffix
        write — prefix blocks are disjoint from ``block_ids`` anyway)."""
        if prefix_ids is None:
            return None
        kp = gather_prefix_kv(c_kv["k"], prefix_ids,
                              scale=c_kv.get("kscale"))
        vp = gather_prefix_kv(c_kv["v"], prefix_ids,
                              scale=c_kv.get("vscale"))
        # block-sharded pools: only the slot's owner gathered real blocks;
        # broadcast so every rank attends the true prefix
        return kv_shard_prefix(shard, kp, vp)

    def group_body(xc, slices):
        stacks_slice, cache_slice = slices
        new_caches = []
        for i, kind in enumerate(pattern):
            xc, k, v = layer_fn(xc, stacks_slice[i], kind, cfg, positions,
                                kv_prefix=prefix_of(cache_slice[i]),
                                shard=shard)
            new_caches.append(write(cache_slice[i], k, v, kind))
        return xc, tuple(new_caches)

    if n_groups > 0:
        x, new_stack_caches = jax.lax.scan(
            group_body, x, (tuple(params["stacks"]), tuple(cache["stacks"])))
        cache = dict(cache, stacks=list(new_stack_caches))
    for i, kind in enumerate(tail):
        p = jax.tree.map(lambda a: a[0], params["tail"][i])
        c_in = jax.tree.map(lambda a: a[0], cache["tail"][i])
        x, k, v = layer_fn(x, p, kind, cfg, positions,
                           kv_prefix=prefix_of(c_in), shard=shard)
        cache["tail"][i] = jax.tree.map(
            lambda a: a[None], write(c_in, k, v, kind))

    x = nn.rms_norm(x, params["final_norm"])
    table_w = params["embed"] if cfg.tie_embeddings else params["unembed"]
    lens = jnp.broadcast_to(n, (b,))
    last = x[jnp.arange(b), lens - 1 - start][:, None]  # last *real* position
    logits = nn.unembed(last, table_w)
    new_len = jax.lax.dynamic_update_slice(
        cache["len"], n[None].astype(jnp.int32), (slot,))
    return logits[:, 0], dict(cache, len=new_len)


# Right-padded prompts are exact for this family (causal attention: real
# positions never attend to pad positions; pad entries beyond ``true_len``
# are masked out of decode by the per-row position vector). Recurrent
# families scan left→right through pad tokens, so they cannot set this.
SUPPORTS_PADDED_PREFILL = True

# The paged pool may store K/V as int8 blocks (+ per-block scales) for this
# family: every serving write path requantizes at write time, so int8
# residency is token-identical to the dense int8 reference.
PAGED_INT8_KV = True


def _prefill_layer(xc, p, kind: str, cfg: ModelConfig, positions, *,
                   kv_prefix=None, shard=None):
    """One prefill layer application; returns (x, this layer's k, v — the
    *newly computed* positions only). Shared by ``prefill`` and
    ``paged_prefill`` so the dense and paged write paths can never diverge
    in how layers are applied.

    ``kv_prefix`` (prefix-cache resume): ``(k, v)`` of the already-cached
    prefix, gathered from the pool. The suffix queries attend
    [prefix ++ suffix] with ``q_offset`` placing row 0 at the global
    position right after the prefix — ``chunked_attention``'s causal and
    window masks then bind by absolute position, so local ("L") layers
    whose full-history window reaches into the prefix stay exact.

    ``shard`` (``cache.KVShard``, heads mode only): slice to the local
    heads, attend locally, all-gather the output; the returned k/v are the
    local-head slice the caller writes into its local pool leaf. Blocks
    mode needs no hook here — prefill math is replicated and the write
    path diverts non-owner ranks to their trash block."""
    h = nn.rms_norm(xc, p["ln1"])
    q, k, v = _project_qkv(h, p, cfg, positions)
    from repro.models.cache import kv_shard_allgather, kv_shard_slice
    q, k, v = kv_shard_slice(shard, q, k, v)
    ka, va, q_off = k, v, 0
    if kv_prefix is not None:
        kp, vp = kv_prefix
        ka = jnp.concatenate([kp.astype(k.dtype), k], axis=2)
        va = jnp.concatenate([vp.astype(v.dtype), v], axis=2)
        q_off = kp.shape[2]
    o = attn.chunked_attention(
        q, ka, va, causal=kind != "B",
        window=cfg.local_window if kind == "L" else None,
        chunk_q=min(cfg.attn_chunk_q, xc.shape[1]),
        q_offset=q_off,
    )
    o = kv_shard_allgather(shard, o)
    xc = xc + nn.dense(_merge_heads(o), p["wo"])
    xc = xc + _mlp(nn.rms_norm(xc, p["ln2"]), p, cfg)
    return xc, k, v


def prefill(params, tokens, cfg: ModelConfig, max_len: int, *, embeds=None,
            true_len=None):
    """Prefill: forward pass + populated float cache; returns (logits, cache).

    Used for the ``prefill_32k`` cells: computes full-sequence logits while
    writing the KV cache (float; quantized serving re-quantizes at decode).

    ``true_len`` (int32 scalar, optional) enables length-bucketed serving
    admission: ``tokens`` may be right-padded to a bucket length, logits are
    taken at position ``true_len - 1`` and the cache position vector is set
    to ``true_len`` so padded entries are never attended during decode.

    When ``serve_quant`` is set, K/V are requantized (static
    ``attn.KV_SCALE``) before the cache fill: int8 serving is int8
    *end-to-end*, prefix positions included — this is what makes the int8
    block pool (which can only hold the requantized integers) bit-identical
    to this dense reference. Storage stays ``compute_dtype`` (the integers
    are exactly representable); attention over the prompt itself runs in
    float either way.
    """
    from repro.models.cache import quantize_kv

    pattern, n_groups, tail = cfg.layer_layout()
    x = embeds if embeds is not None else nn.embed(
        tokens, params["embed"], cfg.compute_dtype)
    b, s = x.shape[:2]
    positions = jnp.arange(s)
    cache = init_cache(cfg, b, max_len, quantized=False)

    def fill(c_kv, k, v, kind):
        if cfg.serve_quant:
            k = quantize_kv(k, attn.KV_SCALE)
            v = quantize_kv(v, attn.KV_SCALE)
        s_len = c_kv["k"].shape[2]
        if s <= s_len:
            pad = s_len - s
            kw = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
            vw = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        else:
            # ring semantics: absolute position p lives at slot p % s_len
            kw = jnp.roll(k[:, :, -s_len:], s % s_len, axis=2)
            vw = jnp.roll(v[:, :, -s_len:], s % s_len, axis=2)
        return {"k": kw.astype(c_kv["k"].dtype), "v": vw.astype(c_kv["v"].dtype)}

    def group_body(xc, slices):
        stacks_slice, cache_slice = slices
        new_caches = []
        for i, kind in enumerate(pattern):
            xc, k, v = _prefill_layer(xc, stacks_slice[i], kind, cfg,
                                      positions)
            new_caches.append(fill(cache_slice[i], k, v, kind))
        return xc, tuple(new_caches)

    if n_groups > 0:
        x, new_stack_caches = jax.lax.scan(
            group_body, x, (tuple(params["stacks"]), tuple(cache["stacks"])))
        cache = dict(cache, stacks=list(new_stack_caches))
    for i, kind in enumerate(tail):
        p = jax.tree.map(lambda a: a[0], params["tail"][i])
        x, k, v = _prefill_layer(x, p, kind, cfg, positions)
        cache["tail"][i] = fill(cache["tail"][i], k, v, kind)

    x = nn.rms_norm(x, params["final_norm"])
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    if true_len is None:
        last = x[:, -1:]
        lens = jnp.full((b,), s, jnp.int32)
    else:
        lens = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32), (b,))
        last = x[jnp.arange(b), lens - 1][:, None]  # last *real* position
    logits = nn.unembed(last, table)
    cache = dict(cache, len=lens)
    return logits[:, 0], cache


# ---------------------------------------------------------------------------
# INT8 serving parameter conversion (the paper's deployment flow)
# ---------------------------------------------------------------------------


def quantize_params(params, cfg: ModelConfig):
    """Float params → QuantizedLinearParams tree for the W8A8 serving path."""
    from repro.kernels.int8_gemm.ops import QuantizedLinearParams

    s = attn.ACT_SCALE

    def qlayer(p):
        out = {}
        for name in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
            w = p[name]

            def quantize_one(wi):
                zero_bias = jnp.zeros((wi.shape[-1],), jnp.float32)
                return QuantizedLinearParams.from_float(wi, zero_bias, s, s)

            out[name] = jax.vmap(quantize_one)(w.astype(jnp.float32))
        return out

    q = {"stacks": [qlayer(st) for st in params["stacks"]]}
    if "tail" in params:
        q["tail"] = [qlayer(t) for t in params["tail"]]
    return q


_QAXES = {
    "wq": ("embed", "heads"), "wk": ("embed", "kv"), "wv": ("embed", "kv"),
    "wo": ("heads", "embed"), "wg": ("embed", "mlp"), "wu": ("embed", "mlp"),
    "wd": ("mlp", "embed"),
}


def quantized_axes(cfg: ModelConfig):
    """Logical axes tree matching ``quantize_params`` output."""
    from repro.kernels.int8_gemm.ops import QuantizedLinearParams

    pattern, n_groups, tail = cfg.layer_layout()

    def qlayer():
        out = {}
        for name, (ain, aout) in _QAXES.items():
            out[name] = QuantizedLinearParams(
                w_q=("layers", ain, aout), bias=("layers", aout),
                mult=("layers", aout), shift=("layers", aout))
        return out

    q = {"stacks": [qlayer() for _ in pattern]}
    if tail:
        q["tail"] = [qlayer() for _ in tail]
    return q
