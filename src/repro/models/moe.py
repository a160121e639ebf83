"""Mixture-of-Experts transformer family (qwen3-moe-30b-a3b, kimi-k2-1t).

Dense GQA attention (shared with the dense family) + top-k routed expert
FFNs. Routing is token-choice with per-batch-row capacity (the switch/t5x
discipline: each batch row is a routing group, so capacity bookkeeping
never crosses data shards — no cross-device prefix sums).

Expert parallelism: expert-stacked weights carry the ``experts`` logical
axis, which the sharding rules map to the ``model`` mesh axis; the
scatter/gather dispatch then induces the all-to-all traffic visible in the
dry-run collective analysis.

Deviation note (DESIGN.md §5): the router runs in f32 softmax for both
archs (kimi-k2's sigmoid+bias routing is approximated by softmax; routing
arithmetic is accuracy-, not performance-relevant here).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import layers as nn
from repro.models import transformer as dense
from repro.models.config import ModelConfig
from repro.models.schema import TensorSpec
from repro.parallel import context as pctx


def _capacity(cfg: ModelConfig, seq: int) -> int:
    c = int(seq * cfg.topk * cfg.moe_capacity / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def _moe_layer_schema(cfg: ModelConfig, n_stack: int) -> Dict[str, TensorSpec]:
    d, hd = cfg.d_model, cfg.hd
    nq, nkv, f, e = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.n_experts
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
        "router": t((d, e), ("embed", "experts")),
        "we_gate": t((e, d, f), ("experts", "embed", "mlp")),
        "we_up": t((e, d, f), ("experts", "embed", "mlp")),
        "we_down": t((e, f, d), ("experts", "mlp", "embed")),
    }


def schema(cfg: ModelConfig):
    pattern, n_groups, tail = cfg.layer_layout()
    s: Dict[str, Any] = {
        "embed": TensorSpec((cfg.vocab, cfg.d_model), ("vocab", "embed_io"),
                            init="embed"),
        "final_norm": TensorSpec((cfg.d_model,), ("embed",), init="zeros"),
        "stacks": [_moe_layer_schema(cfg, n_groups) for _ in pattern],
    }
    if tail:
        s["tail"] = [_moe_layer_schema(cfg, 1) for _ in tail]
    if not cfg.tie_embeddings:
        s["unembed"] = TensorSpec((cfg.vocab, cfg.d_model), ("vocab", "embed_io"))
    return s


import os

_BASELINE_MOE = os.environ.get("REPRO_BASELINE_MOE") == "1"


def moe_mlp(x: jax.Array, p, cfg: ModelConfig) -> jax.Array:
    """Token-choice top-k expert FFN with per-batch-row capacity.

    Dispatch (§Perf iteration, qwen3-moe/kimi-k2): the activation
    scatter-add (``buf.at[...].add(x)`` onto an expert-sharded buffer)
    makes GSPMD replicate the [B,E,C,D] buffer across the model axis —
    catastrophic collectives. Instead we scatter only **int32 slot
    indices** (B·E·C·4 bytes) and GATHER activations into expert order;
    gathers partition cleanly. ``REPRO_BASELINE_MOE=1`` restores the
    scatter path for before/after measurement.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.topk
    cap = _capacity(cfg, s)
    act = nn.ACTIVATIONS[cfg.act]

    # shard_map EP path: active when a mesh context exists with a model
    # axis that divides the expert count (REPRO_MOE_EP=0 disables)
    ctx = pctx.current()
    if (not _BASELINE_MOE and ctx is not None
            and os.environ.get("REPRO_MOE_EP", "1") == "1"):
        mesh, rules = ctx
        m_sz = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        if m_sz > 1 and e % m_sz == 0:
            return _moe_shard_map(x, p, cfg, mesh, rules)

    logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, -1)
    gate, idx = jax.lax.top_k(probs, k)           # [B, S, k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    flat_e = idx.reshape(b, s * k)                 # expert of each slot
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)        # [B, S·k, E]
    pos = jnp.einsum("bte,bte->bt", jnp.cumsum(onehot, 1) - 1, onehot)
    keep = (pos < cap) & (pos >= 0)
    pos_c = jnp.clip(pos, 0, cap - 1)
    bidx = jnp.arange(b)[:, None]

    if _BASELINE_MOE:
        x_rep = jnp.repeat(x, k, axis=1)           # [B, S·k, D]
        contrib = jnp.where(keep[..., None], x_rep, 0)
        buf = jnp.zeros((b, e, cap, d), x.dtype)
        buf = buf.at[bidx, flat_e, pos_c].add(contrib)  # [B, E, C, D]
        buf = pctx.constrain(buf, ("batch", "experts", None, None))
        h = act(
            jnp.einsum("becd,edf->becf", buf, p["we_gate"].astype(x.dtype)),
            jnp.einsum("becd,edf->becf", buf, p["we_up"].astype(x.dtype)),
        )
        out_buf = jnp.einsum("becf,efd->becd", h, p["we_down"].astype(x.dtype))
        y = out_buf[bidx, flat_e, pos_c]           # [B, S·k, D]
        y = jnp.where(keep[..., None], y, 0)
        y = y * gate.reshape(b, s * k, 1).astype(y.dtype)
        return pctx.constrain(y.reshape(b, s, k, d).sum(2),
                              ("batch", None, None))

    # Gather-only permutations (§Perf): invert the slot map once with an
    # int32 scatter (tiny); BOTH directions and both backward passes are
    # gathers (custom_vjp uses the inverse map) — GSPMD partitions gathers
    # cleanly while activation scatters onto expert-sharded buffers
    # replicate across the model axis. Dropped slots scatter out of bounds
    # (mode="drop").
    slot_id = jnp.full((b, e, cap), s * k, jnp.int32)  # s·k = OOB sentinel
    slot_id = slot_id.at[
        bidx, flat_e, jnp.where(keep, pos_c, cap)
    ].set(jnp.arange(s * k)[None, :], mode="drop")
    empty = slot_id >= s * k
    slot_id_c = jnp.minimum(slot_id, s * k - 1)
    token_of_slot = slot_id_c // k                     # [B, E, C]

    buf = _permute_in(k, x, token_of_slot, empty, flat_e, pos_c, keep)
    # two-step layout plan: the permutation is LOCAL under batch sharding
    # (routing never crosses batch rows), then one explicit reshard to the
    # expert layout — GSPMD lowers the reshard to an all-to-all instead of
    # replicating the buffer.
    buf = pctx.constrain(buf, ("batch", None, None, None))
    buf = pctx.constrain(buf, ("batch", "experts", None, None))
    h = act(
        jnp.einsum("becd,edf->becf", buf, p["we_gate"].astype(x.dtype)),
        jnp.einsum("becd,edf->becf", buf, p["we_up"].astype(x.dtype)),
    )
    out_buf = jnp.einsum("becf,efd->becd", h, p["we_down"].astype(x.dtype))
    out_buf = pctx.constrain(out_buf, ("batch", None, None, None))  # reshard
    y = _permute_out(out_buf, flat_e, pos_c, keep, slot_id_c, empty)
    y = y * gate.reshape(b, s * k, 1).astype(y.dtype)
    return pctx.constrain(y.reshape(b, s, k, d).sum(2), ("batch", None, None))


# -- gather-only token↔slot permutations (see moe_mlp docstring) -----------


# NOTE: dims needed by the backward passes are recomputed from static array
# shapes (plus the nondiff `k`), never stashed as Python ints in residuals —
# shard_map's replication-check rewrite turns residual int leaves into
# tracers, which then poison `reshape` shape tuples.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _permute_in(k, x, token_of_slot, empty, flat_e, pos_c, keep):
    """[B,S,D] tokens → [B,E,C,D] expert slots (gather)."""
    b, s, d = x.shape
    _, e, cap = token_of_slot.shape
    buf = jnp.take_along_axis(
        x, token_of_slot.reshape(b, e * cap)[..., None], axis=1
    ).reshape(b, e, cap, d)
    return jnp.where(empty[..., None], 0, buf)


def _permute_in_fwd(k, x, token_of_slot, empty, flat_e, pos_c, keep):
    out = _permute_in(k, x, token_of_slot, empty, flat_e, pos_c, keep)
    return out, (flat_e, pos_c, keep)


def _permute_in_bwd(k, res, dbuf):
    flat_e, pos_c, keep = res
    b, sk = flat_e.shape
    d = dbuf.shape[-1]
    bidx = jnp.arange(b)[:, None]
    dx_slots = dbuf[bidx, flat_e, pos_c]           # gather, not scatter
    dx_slots = jnp.where(keep[..., None], dx_slots, 0)
    dx = dx_slots.reshape(b, sk // k, k, d).sum(2)
    return dx, None, None, None, None, None


_permute_in.defvjp(_permute_in_fwd, _permute_in_bwd)


@jax.custom_vjp
def _permute_out(out_buf, flat_e, pos_c, keep, slot_id_c, empty):
    """[B,E,C,D] expert slots → [B,S·k,D] token slots (gather)."""
    b = out_buf.shape[0]
    bidx = jnp.arange(b)[:, None]
    y = out_buf[bidx, flat_e, pos_c]
    return jnp.where(keep[..., None], y, 0)


def _permute_out_fwd(out_buf, flat_e, pos_c, keep, slot_id_c, empty):
    y = _permute_out(out_buf, flat_e, pos_c, keep, slot_id_c, empty)
    return y, (slot_id_c, empty)


def _permute_out_bwd(res, dy):
    slot_id_c, empty = res
    b, e, cap = slot_id_c.shape
    d = dy.shape[-1]
    dbuf = jnp.take_along_axis(
        dy, slot_id_c.reshape(b, e * cap)[..., None], axis=1
    ).reshape(b, e, cap, d)
    dbuf = jnp.where(empty[..., None], 0, dbuf)
    return dbuf, None, None, None, None, None


_permute_out.defvjp(_permute_out_fwd, _permute_out_bwd)


def _layer(x, p, kind, cfg: ModelConfig, positions):
    h = nn.rms_norm(x, p["ln1"])
    q, k, v = dense._project_qkv(h, p, cfg, positions)
    o = attn.chunked_attention(
        q, k, v, causal=kind != "B",
        window=cfg.local_window if kind == "L" else None,
        chunk_q=min(cfg.attn_chunk_q, x.shape[1]),
    )
    x = x + nn.dense(dense._merge_heads(o), p["wo"])
    x = x + moe_mlp(nn.rms_norm(x, p["ln2"]), p, cfg)
    return pctx.constrain(x, ("batch", None, None))


def forward(params, tokens, cfg: ModelConfig, *, embeds=None):
    pattern, n_groups, tail = cfg.layer_layout()
    x = embeds if embeds is not None else nn.embed(
        tokens, params["embed"], cfg.compute_dtype)
    x = pctx.constrain(x, ("batch", None, None))
    positions = jnp.arange(x.shape[1])

    def apply_group(xc, stacks_slice):
        for kind, p in zip(pattern, stacks_slice):
            xc = _layer(xc, p, kind, cfg, positions)
        return xc

    if cfg.remat:
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


init_cache = dense.init_cache  # same KV cache layout as the dense family
init_paged_cache = dense.init_paged_cache  # …and the same paged pool layout
paged_insert = dense.paged_insert

# int8 KV residency (serve_quant): this family keeps float weights (no
# W8A8 expert GEMMs) but stores/serves the KV cache int8 exactly like the
# dense family — requantize at write time, ITA integer decode attention
PAGED_INT8_KV = True


def _decode_layer(x, p, c, kind, cfg, pos):
    from repro.models.cache import quantize_kv

    h = nn.rms_norm(x, p["ln1"])
    b = x.shape[0]
    hd = cfg.hd
    q = nn.dense(h, p["wq"]).reshape(b, 1, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = nn.dense(h, p["wk"]).reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = nn.dense(h, p["wv"]).reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    q = nn.rope(q, pos[:, None, None], cfg.rope_theta)  # per-row positions
    k = nn.rope(k, pos[:, None, None], cfg.rope_theta)
    if cfg.serve_quant:
        c = dense._cache_write(c, quantize_kv(k, attn.KV_SCALE),
                               quantize_kv(v, attn.KV_SCALE), pos, kind, cfg)
        o = attn.decode_attention_int8(q, c["k"], c["v"], pos + 1, cfg)
    else:
        c = dense._cache_write(c, k, v, pos, kind, cfg)
        o = attn.decode_attention(q, c["k"], c["v"], pos + 1,
                                  ring=kind == "L")
    x = x + nn.dense(dense._merge_heads(o), p["wo"])
    x = x + moe_mlp(nn.rms_norm(x, p["ln2"]), p, cfg)
    return x, c


def decode_step(params, cache, tokens, cfg: ModelConfig, *, qparams=None,
                embeds=None):
    pattern, n_groups, tail = cfg.layer_layout()
    x = embeds if embeds is not None else nn.embed(
        tokens[:, None], params["embed"], cfg.compute_dtype)
    pos = dense._as_positions(cache["len"], x.shape[0])

    def group_body(xc, slices):
        stacks_slice, cache_slice = slices
        new_caches = []
        for i, kind in enumerate(pattern):
            xc, c = _decode_layer(xc, stacks_slice[i], cache_slice[i], kind,
                                  cfg, pos)
            new_caches.append(c)
        return xc, tuple(new_caches)

    if n_groups > 0:
        x, new_caches = jax.lax.scan(
            group_body, x, (tuple(params["stacks"]), tuple(cache["stacks"])))
        cache = dict(cache, stacks=list(new_caches))
    for i, kind in enumerate(tail):
        p = jax.tree.map(lambda a: a[0], params["tail"][i])
        c_in = jax.tree.map(lambda a: a[0], cache["tail"][i])
        x, c = _decode_layer(x, p, c_in, kind, cfg, pos)
        cache["tail"][i] = jax.tree.map(lambda a: a[None], c)
    x = nn.rms_norm(x, params["final_norm"])
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = nn.unembed(x, table)
    return logits[:, 0], dict(cache, len=cache["len"] + 1)


def _paged_decode_layer(x, p, c, kind, cfg, pos, table, attn_backend,
                        shard=None):
    from repro.kernels.paged_attention.ops import (
        paged_attention, paged_attention_int8,
    )
    from repro.models.cache import (
        kv_shard_allgather, kv_shard_owner_rows, kv_shard_slice, quantize_kv,
    )

    h = nn.rms_norm(x, p["ln1"])
    b = x.shape[0]
    hd = cfg.hd
    q = nn.dense(h, p["wq"]).reshape(b, 1, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = nn.dense(h, p["wk"]).reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = nn.dense(h, p["wv"]).reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    q = nn.rope(q, pos[:, None, None], cfg.rope_theta)
    k = nn.rope(k, pos[:, None, None], cfg.rope_theta)
    q, k, v = kv_shard_slice(shard, q, k, v)
    tbl, start = dense._resolve_paged_table(table, kind)
    window = cfg.local_window if kind == "L" else None
    if c["k"].dtype == jnp.int8:   # int8 block pool (serve_quant layout)
        c = dense._paged_cache_write(
            c, quantize_kv(k, attn.KV_SCALE), quantize_kv(v, attn.KV_SCALE),
            pos, tbl, c["k"].shape[2], start=start)
        o = paged_attention_int8(q, c["k"], c["v"], tbl, pos + 1,
                                 k_scale=c["kscale"], v_scale=c["vscale"],
                                 window=window, start=start,
                                 backend=attn_backend)
    else:
        c = dense._paged_cache_write(c, k, v, pos, tbl, c["k"].shape[2],
                                     start=start)
        o = paged_attention(q, c["k"], c["v"], tbl, pos + 1,
                            window=window, start=start, backend=attn_backend)
    o = kv_shard_allgather(shard, o)
    o = kv_shard_owner_rows(shard, o)
    x = x + nn.dense(dense._merge_heads(o), p["wo"])
    x = x + moe_mlp(nn.rms_norm(x, p["ln2"]), p, cfg)
    return x, c


def paged_decode_step(params, cache, tokens, cfg: ModelConfig, table, *,
                      qparams=None, embeds=None,
                      attn_backend: Optional[str] = None,
                      shard=None):
    """One decode step against the paged block pool (see the dense family's
    ``paged_decode_step`` for the block-table and ``shard`` conventions)."""
    del qparams  # MoE serving runs the float path
    pattern, n_groups, tail = cfg.layer_layout()
    x = embeds if embeds is not None else nn.embed(
        tokens[:, None], params["embed"], cfg.compute_dtype)
    pos = dense._as_positions(cache["len"], x.shape[0])
    table = jax.tree.map(lambda a: jnp.asarray(a, jnp.int32), table)

    def group_body(xc, slices):
        stacks_slice, cache_slice = slices
        new_caches = []
        for i, kind in enumerate(pattern):
            xc, c = _paged_decode_layer(
                xc, stacks_slice[i], cache_slice[i], kind, cfg, pos, table,
                attn_backend, shard=shard)
            new_caches.append(c)
        return xc, tuple(new_caches)

    if n_groups > 0:
        x, new_caches = jax.lax.scan(
            group_body, x, (tuple(params["stacks"]), tuple(cache["stacks"])))
        cache = dict(cache, stacks=list(new_caches))
    for i, kind in enumerate(tail):
        p = jax.tree.map(lambda a: a[0], params["tail"][i])
        c_in = jax.tree.map(lambda a: a[0], cache["tail"][i])
        x, c = _paged_decode_layer(x, p, c_in, kind, cfg, pos, table,
                                   attn_backend, shard=shard)
        cache["tail"][i] = jax.tree.map(lambda a: a[None], c)
    x = nn.rms_norm(x, params["final_norm"])
    tbl = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = nn.unembed(x, tbl)
    return logits[:, 0], dict(cache, len=cache["len"] + 1)


def _paged_verify_layer(x, p, c, kind, cfg, pos, table, attn_backend):
    """Small-q speculative-verify layer (see the dense family's
    ``_paged_verify_layer``). The expert router sees all Q = spec + 1
    positions of every slot as one routing group per row, with capacity
    ``_capacity(cfg, Q)`` — token identity with the q=1 decode path
    requires the capacity not to bind, the same no-drop condition the
    prefix-cache resume already pins down."""
    from repro.kernels.paged_attention.ops import (
        paged_attention_verify, paged_attention_verify_int8,
    )
    from repro.models.cache import quantize_kv

    h = nn.rms_norm(x, p["ln1"])
    b, qlen = x.shape[:2]
    hd = cfg.hd
    q = nn.dense(h, p["wq"]).reshape(b, qlen, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = nn.dense(h, p["wk"]).reshape(b, qlen, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = nn.dense(h, p["wv"]).reshape(b, qlen, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    positions = pos[:, None] + jnp.arange(qlen, dtype=jnp.int32)[None, :]
    q = nn.rope(q, positions[:, None, :], cfg.rope_theta)
    k = nn.rope(k, positions[:, None, :], cfg.rope_theta)
    tbl, start = dense._resolve_paged_table(table, kind)
    window = cfg.local_window if kind == "L" else None
    if c["k"].dtype == jnp.int8:
        c = dense._paged_verify_write(
            c, quantize_kv(k, attn.KV_SCALE), quantize_kv(v, attn.KV_SCALE),
            pos, tbl, c["k"].shape[2], start=start)
        o = paged_attention_verify_int8(
            q, c["k"], c["v"], tbl, pos + 1,
            k_scale=c["kscale"], v_scale=c["vscale"],
            window=window, start=start, backend=attn_backend)
    else:
        c = dense._paged_verify_write(c, k, v, pos, tbl, c["k"].shape[2],
                                      start=start)
        o = paged_attention_verify(q, c["k"], c["v"], tbl, pos + 1,
                                   window=window, start=start,
                                   backend=attn_backend)
    x = x + nn.dense(dense._merge_heads(o), p["wo"])
    x = x + moe_mlp(nn.rms_norm(x, p["ln2"]), p, cfg)
    return x, c


def paged_verify_step(params, cache, tokens, cfg: ModelConfig, table, *,
                      qparams=None, attn_backend: Optional[str] = None):
    """Speculative-decode verify step (see the dense family's
    ``paged_verify_step`` for the contract): ``tokens`` [slots, Q] int32,
    returns ``(logits [slots, Q, V], cache)`` with ``cache["len"]``
    untouched — the engine owns the committed frontier."""
    del qparams  # MoE serving runs the float path
    pattern, n_groups, tail = cfg.layer_layout()
    x = nn.embed(tokens, params["embed"], cfg.compute_dtype)
    pos = dense._as_positions(cache["len"], x.shape[0])
    table = jax.tree.map(lambda a: jnp.asarray(a, jnp.int32), table)

    def group_body(xc, slices):
        stacks_slice, cache_slice = slices
        new_caches = []
        for i, kind in enumerate(pattern):
            xc, c = _paged_verify_layer(
                xc, stacks_slice[i], cache_slice[i], kind, cfg, pos, table,
                attn_backend)
            new_caches.append(c)
        return xc, tuple(new_caches)

    if n_groups > 0:
        x, new_caches = jax.lax.scan(
            group_body, x, (tuple(params["stacks"]), tuple(cache["stacks"])))
        cache = dict(cache, stacks=list(new_caches))
    for i, kind in enumerate(tail):
        p = jax.tree.map(lambda a: a[0], params["tail"][i])
        c_in = jax.tree.map(lambda a: a[0], cache["tail"][i])
        x, c = _paged_verify_layer(x, p, c_in, kind, cfg, pos, table,
                                   attn_backend)
        cache["tail"][i] = jax.tree.map(lambda a: a[None], c)
    x = nn.rms_norm(x, params["final_norm"])
    tbl = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return nn.unembed(x, tbl), cache


def _prefill_layer(xc, p, kind, cfg: ModelConfig, positions, *,
                   kv_prefix=None, shard=None):
    """One prefill layer application; returns (x, this layer's k, v — the
    newly computed positions only). Shared by ``prefill`` and
    ``paged_prefill`` so the two write paths can never diverge in how
    layers are applied. ``kv_prefix`` resumes a prefix-cache hit exactly
    as in the dense family (suffix queries attend [prefix ++ suffix] at
    ``q_offset``); note the expert router below still only sees the
    *suffix* tokens — cached-prefix tokens are never re-routed, which is
    the point, but it means ``_capacity`` is sized to the suffix length."""
    from repro.models.cache import kv_shard_allgather, kv_shard_slice

    h = nn.rms_norm(xc, p["ln1"])
    q, k, v = dense._project_qkv(h, p, cfg, positions)
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
        q_offset=q_off)
    o = kv_shard_allgather(shard, o)
    xc = xc + nn.dense(dense._merge_heads(o), p["wo"])
    xc = xc + moe_mlp(nn.rms_norm(xc, p["ln2"]), p, cfg)
    return xc, k, v


def prefill(params, tokens, cfg: ModelConfig, max_len: int, *, embeds=None):
    """MoE prefill: forward + populated cache. Under ``serve_quant`` the
    K/V are requantized at write time (int8-end-to-end residency, same as
    the dense family) so the int8 block pool is bit-identical to this
    dense reference."""
    from repro.models.cache import quantize_kv

    pattern, n_groups, tail = cfg.layer_layout()
    x = embeds if embeds is not None else nn.embed(
        tokens, params["embed"], cfg.compute_dtype)
    b, s = x.shape[:2]
    positions = jnp.arange(s)
    cache = init_cache(cfg, b, max_len, quantized=False)

    def fill(c_kv, k, v):
        if cfg.serve_quant:
            k = quantize_kv(k, attn.KV_SCALE)
            v = quantize_kv(v, attn.KV_SCALE)
        s_len = c_kv["k"].shape[2]
        if s <= s_len:
            pad = ((0, 0), (0, 0), (0, s_len - s), (0, 0))
            kw, vw = jnp.pad(k, pad), jnp.pad(v, pad)
        else:
            # ring semantics (as in the dense family): absolute position p
            # lives at slot p % s_len, so decode's ring write evicts the
            # oldest in-window position, not an arbitrary one
            kw = jnp.roll(k[:, :, -s_len:], s % s_len, axis=2)
            vw = jnp.roll(v[:, :, -s_len:], s % s_len, axis=2)
        return {"k": kw.astype(c_kv["k"].dtype),
                "v": vw.astype(c_kv["v"].dtype)}

    def group_body(xc, slices):
        stacks_slice, cache_slice = slices
        new_caches = []
        for i, kind in enumerate(pattern):
            xc, k, v = _prefill_layer(xc, stacks_slice[i], kind, cfg,
                                      positions)
            new_caches.append(fill(cache_slice[i], k, v))
        return xc, tuple(new_caches)

    if n_groups > 0:
        x, new_caches = jax.lax.scan(
            group_body, x, (tuple(params["stacks"]), tuple(cache["stacks"])))
        cache = dict(cache, stacks=list(new_caches))
    for i, kind in enumerate(tail):  # layers past the last full group
        p = jax.tree.map(lambda a: a[0], params["tail"][i])
        c_in = jax.tree.map(lambda a: a[0], cache["tail"][i])
        x, k, v = _prefill_layer(x, p, kind, cfg, positions)
        cache["tail"][i] = jax.tree.map(lambda a: a[None], fill(c_in, k, v))
    x = nn.rms_norm(x, params["final_norm"])
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = nn.unembed(x[:, -1:], table)
    return logits[:, 0], dict(cache, len=jnp.full((b,), s, jnp.int32))


def paged_prefill(params, tokens, cfg: ModelConfig, cache, slot, block_ids,
                  *, ring_ids=None, true_len=None, embeds=None,
                  prefix_ids=None, start=0, shard=None):
    """MoE prefill straight into pool blocks: the dense family's shared
    scaffold with this family's expert-FFN layer (see ``dense.
    _paged_prefill_impl`` for the write conventions). ``tokens`` should be
    the exact prompt (no bucket padding): pad tokens would enlarge the
    routing capacity ``_capacity(cfg, s)`` and could change which real
    tokens overflow — the K/V writes pad to block granularity instead.

    Prefix-cache resume (``prefix_ids``/``start``): cached-prefix tokens
    are not re-run through the router (their K/V comes from the pool), so
    the routing capacity is sized to the *suffix* — identical routing to
    the cache-off engine requires the capacity not to bind, which the
    token-identity matrix pins down."""
    return dense._paged_prefill_impl(
        params, tokens, cfg, cache, slot, block_ids, layer_fn=_prefill_layer,
        ring_ids=ring_ids, true_len=true_len, embeds=embeds,
        prefix_ids=prefix_ids, start=start, shard=shard)


# ---------------------------------------------------------------------------
# shard_map expert parallelism (§Perf beyond-paper, qwen3/kimi)
# ---------------------------------------------------------------------------
#
# In the 2D (data, model) mesh, activations are REPLICATED across the model
# axis — so each model-rank can gather the tokens routed to its local
# experts with purely LOCAL index ops, run its expert FFNs, and contribute a
# partial output; one psum over 'model' combines. The only cross-chip
# traffic is that psum (2·B·S·D per layer) — no all-to-all, no replicated
# dispatch buffers. Grads flow through shard_map natively (psum^T = id).


def _moe_shard_map(x, p, cfg: ModelConfig, mesh, rules):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    batch_ax = rules.mesh_axes("batch", mesh)
    e, k = cfg.n_experts, cfg.topk
    m = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    cap = _capacity(cfg, x.shape[1])
    act = nn.ACTIVATIONS[cfg.act]

    def body(x_b, router_b, wg_b, wu_b, wd_b):
        b, s, d = x_b.shape
        e_loc = wg_b.shape[0]
        rank = jax.lax.axis_index("model")
        # declare x varying over 'model': each rank contributes a partial
        # dx, and this cast's transpose is the psum that sums them
        x_b = jax.lax.pcast(x_b, ("model",), to="varying")
        router_b = jax.lax.pcast(router_b, ("model",), to="varying")
        logits = jnp.einsum("bsd,de->bse", x_b.astype(jnp.float32),
                            router_b.astype(jnp.float32))
        probs = jax.nn.softmax(logits, -1)
        gate, idx = jax.lax.top_k(probs, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        flat_e = idx.reshape(b, s * k)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos = jnp.einsum("bte,bte->bt", jnp.cumsum(onehot, 1) - 1, onehot)
        keep = (pos < cap) & (pos >= 0)
        pos_c = jnp.clip(pos, 0, cap - 1)
        bidx = jnp.arange(b)[:, None]

        # local slot inversion. NOTE: negative indices WRAP in jnp .at[]
        # before the OOB check, so foreign experts must be redirected to a
        # positive out-of-range index for mode="drop" to discard them.
        loc_e = flat_e - rank * e_loc
        mine_e = (loc_e >= 0) & (loc_e < e_loc)
        loc_e_safe = jnp.where(mine_e, loc_e, e_loc)
        slot_id = jnp.full((b, e_loc, cap), s * k, jnp.int32)
        slot_id = slot_id.at[
            bidx, loc_e_safe, jnp.where(keep, pos_c, cap)
        ].set(jnp.arange(s * k)[None, :], mode="drop")
        empty = slot_id >= s * k
        slot_id_c = jnp.minimum(slot_id, s * k - 1)
        token_of_slot = slot_id_c // k

        # bwd of _permute_in gathers dbuf at (expert, pos): restrict to
        # slots this rank OWNS (foreign contributions arrive via the psum
        # from their owning ranks)
        buf = _permute_in(k, x_b, token_of_slot, empty,
                          jnp.clip(loc_e, 0, e_loc - 1), pos_c,
                          keep & mine_e)
        h = act(
            jnp.einsum("becd,edf->becf", buf, wg_b.astype(x_b.dtype)),
            jnp.einsum("becd,edf->becf", buf, wu_b.astype(x_b.dtype)),
        )
        out_buf = jnp.einsum("becf,efd->becd", h, wd_b.astype(x_b.dtype))
        # combine locally: slots owned by other ranks read garbage — zero
        # them via the ownership mask before the cross-rank psum
        mine = mine_e & keep
        y = out_buf[bidx, jnp.clip(loc_e, 0, e_loc - 1), pos_c]
        y = jnp.where(mine[..., None], y, 0)
        y = y * gate.reshape(b, s * k, 1).astype(y.dtype)
        y = y.reshape(b, s, k, d).sum(2)
        return jax.lax.psum(y, "model")

    fm = shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_ax, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(batch_ax, None, None),
    )
    return fm(x, p["router"], p["we_gate"], p["we_up"], p["we_down"])
