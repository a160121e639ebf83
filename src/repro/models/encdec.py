"""Whisper-style encoder-decoder family (``whisper-small``).

Encoder: bidirectional self-attention over precomputed mel-frame embeddings
(the conv frontend is a STUB per the assignment — ``input_specs`` supplies
[B, enc_seq, d] frame embeddings). Decoder: causal self-attention +
cross-attention to encoder states + MLP.

Positions use RoPE as the structural stand-in for Whisper's sinusoidal
absolute embeddings (identical FLOPs/memory; noted in DESIGN.md).

Int8 KV residency (``serve_quant``): the decoder's self-attention K/V —
the only cache that grows with decode position — is requantized at write
time and served through the ITA integer pipeline (int8 blocks on the
paged layout); weights and the fixed-size cross K/V arena stay float.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import layers as nn
from repro.models import transformer as dense
from repro.models.config import ModelConfig
from repro.models.schema import TensorSpec


def _xattn_layer_schema(cfg: ModelConfig, n_stack: int) -> Dict[str, TensorSpec]:
    """Decoder layer: self-attn + cross-attn + MLP."""
    base = dense._layer_schema(cfg, n_stack)
    d, hd, nq, nkv = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    L = ("layers",)

    def t(shape, axes, **kw):
        return TensorSpec((n_stack, *shape), L + axes, **kw)

    base.update({
        "lnx": t((d,), ("embed",), init="zeros"),
        "xwq": t((d, nq * hd), ("embed", "heads")),
        "xwk": t((d, nkv * hd), ("embed", "kv")),
        "xwv": t((d, nkv * hd), ("embed", "kv")),
        "xwo": t((nq * hd, d), ("heads", "embed")),
    })
    return base


def schema(cfg: ModelConfig):
    return {
        "embed": TensorSpec((cfg.vocab, cfg.d_model), ("vocab", "embed_io"),
                            init="embed"),
        "enc_stack": dense._layer_schema(cfg, cfg.n_enc_layers),
        "enc_norm": TensorSpec((cfg.d_model,), ("embed",), init="zeros"),
        "dec_stack": _xattn_layer_schema(cfg, cfg.n_layers),
        "final_norm": TensorSpec((cfg.d_model,), ("embed",), init="zeros"),
        "unembed": TensorSpec((cfg.vocab, cfg.d_model), ("vocab", "embed_io")),
    }


def encode(params, frame_embeds, cfg: ModelConfig):
    """[B, S_enc, D] frame embeddings → encoder states."""
    x = frame_embeds.astype(cfg.compute_dtype)
    positions = jnp.arange(x.shape[1])

    def apply_layer(xc, p):
        return dense._layer(xc, p, "B", cfg, positions)

    if cfg.remat:
        apply_layer = jax.checkpoint(apply_layer)

    def body(xc, p):
        return apply_layer(xc, p), None

    x, _ = jax.lax.scan(body, x, params["enc_stack"])
    return nn.rms_norm(x, params["enc_norm"])


def _cross_attn(x, p, enc_kv, cfg):
    """Cross-attention using precomputed encoder K/V."""
    b, s, _ = x.shape
    hd = cfg.hd
    h = nn.rms_norm(x, p["lnx"])
    q = nn.dense(h, p["xwq"]).reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k, v = enc_kv
    o = attn.chunked_attention(q, k, v, causal=False,
                               chunk_q=min(cfg.attn_chunk_q, s))
    return x + nn.dense(dense._merge_heads(o), p["xwo"])


def _enc_kv(p, enc, cfg):
    b, se, _ = enc.shape
    hd = cfg.hd
    k = nn.dense(enc, p["xwk"]).reshape(b, se, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = nn.dense(enc, p["xwv"]).reshape(b, se, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    return k, v


def forward(params, tokens, cfg: ModelConfig, *, embeds=None):
    """Teacher forcing: ``embeds`` = encoder frame embeddings (stub input)."""
    if embeds is None:
        raise ValueError("encdec forward needs frame embeddings (stub input)")
    enc = encode(params, embeds, cfg)
    x = nn.embed(tokens, params["embed"], cfg.compute_dtype)
    positions = jnp.arange(x.shape[1])

    def apply_layer(xc, p):
        h = nn.rms_norm(xc, p["ln1"])
        q, k, v = dense._project_qkv(h, p, cfg, positions)
        o = attn.chunked_attention(q, k, v, causal=True,
                                   chunk_q=min(cfg.attn_chunk_q, xc.shape[1]))
        xc = xc + nn.dense(dense._merge_heads(o), p["wo"])
        xc = _cross_attn(xc, p, _enc_kv(p, enc, cfg), cfg)
        xc = xc + dense._mlp(nn.rms_norm(xc, p["ln2"]), p, cfg)
        return xc

    if cfg.remat:
        apply_layer = jax.checkpoint(apply_layer)

    def body(xc, p):
        return apply_layer(xc, p), None

    x, _ = jax.lax.scan(body, x, params["dec_stack"])
    x = nn.rms_norm(x, params["final_norm"])
    return nn.unembed(x, params["unembed"])


# self-attention KV may live in int8 blocks on the paged layout (cross K/V
# stays a float dense arena): write paths requantize identically to the
# dense serve_quant reference
PAGED_INT8_KV = True


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *, quantized=None):
    hd, nkv = cfg.hd, cfg.n_kv_heads
    L = cfg.n_layers
    return {
        "k": jnp.zeros((L, batch, nkv, max_len, hd), cfg.compute_dtype),
        "v": jnp.zeros((L, batch, nkv, max_len, hd), cfg.compute_dtype),
        "xk": jnp.zeros((L, batch, nkv, cfg.enc_seq, hd), cfg.compute_dtype),
        "xv": jnp.zeros((L, batch, nkv, cfg.enc_seq, hd), cfg.compute_dtype),
        "len": jnp.zeros((batch,), jnp.int32),  # per-row position vector
    }


def _dec_prefill_layer(xc, p, enc, cfg: ModelConfig, positions, *,
                       kv_prefix=None, shard=None):
    """One decoder-layer prefill application; returns (x, k, v, xk, xv —
    the newly computed positions only). Shared by ``prefill`` and
    ``paged_prefill`` so the dense and paged write paths can never diverge
    in how layers are applied. ``kv_prefix`` resumes a prefix-cache hit:
    self-attention runs [prefix ++ suffix] at ``q_offset`` (cross
    attention is position-free — unchanged). ``shard`` (heads mode): only
    the paged *self*-attention is head-sliced + output-all-gathered; the
    fixed-size cross-attention arena stays replicated."""
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
    o = attn.chunked_attention(q, ka, va, causal=True,
                               chunk_q=min(cfg.attn_chunk_q, xc.shape[1]),
                               q_offset=q_off)
    o = kv_shard_allgather(shard, o)
    xc = xc + nn.dense(dense._merge_heads(o), p["wo"])
    xk, xv = _enc_kv(p, enc, cfg)
    xc = _cross_attn(xc, p, (xk, xv), cfg)
    xc = xc + dense._mlp(nn.rms_norm(xc, p["ln2"]), p, cfg)
    return xc, k, v, xk, xv


def prefill(params, tokens, cfg: ModelConfig, max_len: int, *, embeds=None):
    """Encode audio + ingest decoder prompt; cache cross-K/V per layer.

    Under ``serve_quant`` the decoder's *self*-attention K/V are
    requantized at write time (the int8-end-to-end residency shared with
    the dense family, making the int8 block pool bit-identical to this
    reference); cross K/V stay float — they are a fixed-size encoder-side
    arena, not paged residency."""
    from repro.models.cache import quantize_kv

    if embeds is None:
        raise ValueError("encdec prefill needs frame embeddings (stub input)")
    enc = encode(params, embeds, cfg)
    x = nn.embed(tokens, params["embed"], cfg.compute_dtype)
    b, s = x.shape[:2]
    positions = jnp.arange(s)
    cache = init_cache(cfg, b, max_len)

    def body(xc, p):
        xc, k, v, xk, xv = _dec_prefill_layer(xc, p, enc, cfg, positions)
        if cfg.serve_quant:
            k = quantize_kv(k, attn.KV_SCALE)
            v = quantize_kv(v, attn.KV_SCALE)
        kw = jnp.pad(k, ((0, 0), (0, 0), (0, max_len - s), (0, 0)))
        vw = jnp.pad(v, ((0, 0), (0, 0), (0, max_len - s), (0, 0)))
        return xc, (kw.astype(cfg.compute_dtype), vw.astype(cfg.compute_dtype),
                    xk.astype(cfg.compute_dtype), xv.astype(cfg.compute_dtype))

    x, (ks, vs, xks, xvs) = jax.lax.scan(body, x, params["dec_stack"])
    x = nn.rms_norm(x, params["final_norm"])
    logits = nn.unembed(x[:, -1:], params["unembed"])
    return logits[:, 0], {"k": ks, "v": vs, "xk": xks, "xv": xvs,
                          "len": jnp.full((b,), s, jnp.int32)}


def init_paged_cache(cfg: ModelConfig, slots: int, layout, *, quantized=None):
    """Paged self-attention KV pools + dense per-slot cross-attention cache.

    Only the decoder's *self*-attention KV grows with decode position, so
    only it is paged (``[L, num_blocks, Hkv, block_len, hd]`` shared pools);
    the encoder-side cross K/V is a fixed ``enc_seq``-length per-slot arena.

    ``quantized`` (default ``cfg.serve_quant``) stores the self-attention
    pools as int8 blocks plus per-block scale vectors (static
    ``attn.KV_SCALE`` calibration) — the growing, paged residency is what
    the int8 halving targets; the fixed-size cross K/V arena stays in
    ``compute_dtype``.
    """
    if quantized is None:
        quantized = cfg.serve_quant
    hd, nkv = cfg.hd, cfg.n_kv_heads
    L = cfg.n_layers
    dt = cfg.compute_dtype
    pool_dt = jnp.int8 if quantized else dt
    pool = (L, layout.num_blocks, nkv, layout.block_len, hd)
    cache = {
        "k": jnp.zeros(pool, pool_dt),
        "v": jnp.zeros(pool, pool_dt),
        "xk": jnp.zeros((L, slots, nkv, cfg.enc_seq, hd), dt),
        "xv": jnp.zeros((L, slots, nkv, cfg.enc_seq, hd), dt),
        "len": jnp.zeros((slots,), jnp.int32),
    }
    if quantized:
        # distinct buffers: engines donate the cache pytree (see dense)
        cache["kscale"] = jnp.full((L, layout.num_blocks), attn.KV_SCALE,
                                   jnp.float32)
        cache["vscale"] = jnp.full((L, layout.num_blocks), attn.KV_SCALE,
                                   jnp.float32)
    return cache


def paged_insert(cache, single, slot, block_ids, cfg: ModelConfig):
    """Splice a batch-1 prefill into pool blocks (self-attn) and the slot
    row (cross-attn)."""
    from repro.models.cache import cache_insert, paged_insert_kv

    block_ids = jnp.asarray(block_ids, jnp.int32)
    out = dict(cache)
    out["k"] = paged_insert_kv(cache["k"], single["k"], block_ids)
    out["v"] = paged_insert_kv(cache["v"], single["v"], block_ids)
    dense_part = cache_insert(
        {"xk": cache["xk"], "xv": cache["xv"], "len": cache["len"]},
        {"xk": single["xk"], "xv": single["xv"], "len": single["len"]},
        slot)
    out.update(dense_part)
    return out


def paged_prefill(params, tokens, cfg: ModelConfig, cache, slot, block_ids,
                  *, ring_ids=None, true_len=None, embeds=None,
                  prefix_ids=None, start=0, shard=None):
    """Encode audio + ingest decoder prompt straight into the paged cache:
    self-attention K/V lands in pool blocks (bulk block writes, tail at
    block granularity), cross-attention K/V and the position counter land
    in ``slot``'s dense rows. No intermediate dense cache, no splice.
    Int8 pools requantize before the block write (same write-time
    requantization as the dense reference).

    Prefix-cache resume (``prefix_ids``/``start``): ``tokens`` carries
    only the uncached decoder-prompt suffix; each layer gathers the cached
    prefix K/V from its pool and the suffix attends [prefix ++ suffix] at
    ``q_offset=start``. The encoder and the per-slot cross K/V always run
    in full — they are per-request (``embeds``-dependent), not shareable
    block residency."""
    from repro.models.cache import (
        gather_prefix_kv, kv_shard_prefix, prefill_write_kv, quantize_kv,
    )

    if ring_ids is not None:
        raise ValueError(
            "encdec has no sliding-window layers: ring_ids must be None "
            "(a ring table/start layout would be read incorrectly)")
    if embeds is None:
        raise ValueError("encdec prefill needs frame embeddings (stub input)")
    enc = encode(params, embeds, cfg)
    x = nn.embed(tokens, params["embed"], cfg.compute_dtype)
    b, s = x.shape[:2]
    start = int(start)
    positions = start + jnp.arange(s)
    block_ids = jnp.asarray(block_ids, jnp.int32)
    if prefix_ids is not None:
        prefix_ids = jnp.asarray(prefix_ids, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    n = jnp.asarray(start + s if true_len is None else true_len, jnp.int32)
    L = cfg.n_layers
    # per-layer scale rows ride the scan for the int8 prefix gather (the
    # zeros fallback is never indexed on float pools)
    ks_in = cache.get("kscale", jnp.zeros((L, 1), jnp.float32))
    vs_in = cache.get("vscale", jnp.zeros((L, 1), jnp.float32))

    def body(carry, slices):
        xc = carry
        p, kc, vc, ksc, vsc = slices
        kv_prefix = None
        if prefix_ids is not None:
            kv_prefix = kv_shard_prefix(
                shard,
                gather_prefix_kv(kc, prefix_ids, scale=ksc),
                gather_prefix_kv(vc, prefix_ids, scale=vsc))
        xc, k, v, xk, xv = _dec_prefill_layer(xc, p, enc, cfg, positions,
                                              kv_prefix=kv_prefix,
                                              shard=shard)
        if kc.dtype == jnp.int8:   # int8 block pool (serve_quant layout)
            k = quantize_kv(k, attn.KV_SCALE)
            v = quantize_kv(v, attn.KV_SCALE)
        kc = prefill_write_kv(kc, k, block_ids)
        vc = prefill_write_kv(vc, v, block_ids)
        return xc, (kc, vc, xk.astype(cfg.compute_dtype),
                    xv.astype(cfg.compute_dtype))

    x, (ks, vs, xks, xvs) = jax.lax.scan(
        body, x, (params["dec_stack"], cache["k"], cache["v"], ks_in, vs_in))
    x = nn.rms_norm(x, params["final_norm"])
    lens = jnp.broadcast_to(n, (b,))
    last = x[jnp.arange(b), lens - 1 - start][:, None]
    logits = nn.unembed(last, params["unembed"])
    out = dict(cache, k=ks, v=vs)
    out["xk"] = jax.lax.dynamic_update_slice_in_dim(
        cache["xk"], xks.astype(cache["xk"].dtype), slot, axis=1)
    out["xv"] = jax.lax.dynamic_update_slice_in_dim(
        cache["xv"], xvs.astype(cache["xv"].dtype), slot, axis=1)
    out["len"] = jax.lax.dynamic_update_slice(
        cache["len"], n[None].astype(jnp.int32), (slot,))
    return logits[:, 0], out


def paged_decode_step(params, cache, tokens, cfg: ModelConfig, table, *,
                      qparams=None, embeds=None,
                      attn_backend: Optional[str] = None,
                      shard=None):
    """One decode step with paged self-attention KV (cross K/V stays dense).

    Int8 block pools take ``paged_attention_int8`` (requantized write +
    ITA/xla or fused-kernel attention over the int8 blocks); the per-layer
    scale vectors ride through the scan alongside the pools. ``shard``
    (``cache.KVShard``): only the paged self-attention is sharded; the
    per-slot cross K/V arena is replicated in both modes."""
    from repro.kernels.paged_attention.ops import (
        paged_attention, paged_attention_int8,
    )
    from repro.models.cache import (
        kv_shard_allgather, kv_shard_owner_rows, kv_shard_slice, quantize_kv,
    )

    del qparams
    x = nn.embed(tokens[:, None], params["embed"], cfg.compute_dtype)
    b = x.shape[0]
    pos = dense._as_positions(cache["len"], b)
    table = jax.tree.map(lambda a: jnp.asarray(a, jnp.int32), table)
    # self-attention is always global in this family — resolve as kind "G"
    # (start is always None for global layers; no window plumbing applies)
    tbl, _ = dense._resolve_paged_table(table, "G")
    hd = cfg.hd
    int8_kv = cache["k"].dtype == jnp.int8

    def body(xc, slices):
        p, kc, vc, ksc, vsc, xkc, xvc = slices
        h = nn.rms_norm(xc, p["ln1"])
        q = nn.dense(h, p["wq"]).reshape(b, 1, cfg.n_heads, hd).transpose(0, 2, 1, 3)
        k = nn.dense(h, p["wk"]).reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
        v = nn.dense(h, p["wv"]).reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
        q = nn.rope(q, pos[:, None, None], cfg.rope_theta)
        k = nn.rope(k, pos[:, None, None], cfg.rope_theta)
        q, k, v = kv_shard_slice(shard, q, k, v)
        if int8_kv:
            k, v = quantize_kv(k, attn.KV_SCALE), quantize_kv(v, attn.KV_SCALE)
        sc = dense._paged_cache_write({"k": kc, "v": vc}, k, v, pos, tbl,
                                      kc.shape[2])
        kc, vc = sc["k"], sc["v"]
        if int8_kv:
            o = paged_attention_int8(q, kc, vc, tbl, pos + 1,
                                     k_scale=ksc, v_scale=vsc,
                                     backend=attn_backend)
        else:
            o = paged_attention(q, kc, vc, tbl, pos + 1, backend=attn_backend)
        o = kv_shard_allgather(shard, o)
        o = kv_shard_owner_rows(shard, o)
        xc = xc + nn.dense(dense._merge_heads(o), p["wo"])
        hx = nn.rms_norm(xc, p["lnx"])
        xq = nn.dense(hx, p["xwq"]).reshape(b, 1, cfg.n_heads, hd).transpose(0, 2, 1, 3)
        xo = attn.decode_attention(xq, xkc, xvc, jnp.asarray(cfg.enc_seq, jnp.int32))
        xc = xc + nn.dense(dense._merge_heads(xo), p["xwo"])
        xc = xc + dense._mlp(nn.rms_norm(xc, p["ln2"]), p, cfg)
        return xc, (kc, vc)

    L = cfg.n_layers
    ks_in = cache.get("kscale", jnp.zeros((L, 1), jnp.float32))
    vs_in = cache.get("vscale", jnp.zeros((L, 1), jnp.float32))
    x, (ks, vs) = jax.lax.scan(
        body, x, (params["dec_stack"], cache["k"], cache["v"],
                  ks_in, vs_in, cache["xk"], cache["xv"]))
    x = nn.rms_norm(x, params["final_norm"])
    logits = nn.unembed(x, params["unembed"])
    return logits[:, 0], dict(cache, k=ks, v=vs, len=cache["len"] + 1)


def paged_verify_step(params, cache, tokens, cfg: ModelConfig, table, *,
                      qparams=None, embeds=None,
                      attn_backend: Optional[str] = None):
    """Speculative-decode verify step (see ``transformer.paged_verify_step``
    for the token/position contract): ``tokens`` [slots, Q] scores all
    Q = spec_tokens + 1 positions per slot in one dispatch. Self-attention
    runs the multi-q verify ops over the paged pool; cross-attention folds
    the Q axis into the head axis of ``decode_attention`` — every (head, j)
    row attends the same full ``enc_seq`` arena, so each row is bit-identical
    to the decode path's single-query cross-attention. ``cache["len"]`` is
    host-owned and not advanced here."""
    from repro.kernels.paged_attention.ops import (
        paged_attention_verify, paged_attention_verify_int8,
    )
    from repro.models.cache import quantize_kv

    del qparams  # encdec serving keeps float weights
    x = nn.embed(tokens, params["embed"], cfg.compute_dtype)
    b, qlen = tokens.shape
    pos = dense._as_positions(cache["len"], b)
    positions = pos[:, None] + jnp.arange(qlen, dtype=jnp.int32)[None, :]
    table = jax.tree.map(lambda a: jnp.asarray(a, jnp.int32), table)
    tbl, _ = dense._resolve_paged_table(table, "G")
    hd = cfg.hd
    int8_kv = cache["k"].dtype == jnp.int8

    def body(xc, slices):
        p, kc, vc, ksc, vsc, xkc, xvc = slices
        h = nn.rms_norm(xc, p["ln1"])
        q = nn.dense(h, p["wq"]).reshape(b, qlen, cfg.n_heads, hd).transpose(0, 2, 1, 3)
        k = nn.dense(h, p["wk"]).reshape(b, qlen, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
        v = nn.dense(h, p["wv"]).reshape(b, qlen, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
        q = nn.rope(q, positions[:, None, :], cfg.rope_theta)
        k = nn.rope(k, positions[:, None, :], cfg.rope_theta)
        if int8_kv:
            k, v = quantize_kv(k, attn.KV_SCALE), quantize_kv(v, attn.KV_SCALE)
        sc = dense._paged_verify_write({"k": kc, "v": vc}, k, v, pos, tbl,
                                       kc.shape[2])
        kc, vc = sc["k"], sc["v"]
        if int8_kv:
            o = paged_attention_verify_int8(q, kc, vc, tbl, pos + 1,
                                            k_scale=ksc, v_scale=vsc,
                                            backend=attn_backend)
        else:
            o = paged_attention_verify(q, kc, vc, tbl, pos + 1,
                                       backend=attn_backend)
        xc = xc + nn.dense(dense._merge_heads(o), p["wo"])
        hx = nn.rms_norm(xc, p["lnx"])
        xq = nn.dense(hx, p["xwq"]).reshape(b, qlen, cfg.n_heads, hd).transpose(0, 2, 1, 3)
        # fold Q into the query-head axis: rows flatten kv-major, so row
        # (h, j) still lands in kv group h // group — a uniform-length
        # (position-free) attention identical per row to the decode path
        xo = attn.decode_attention(
            xq.reshape(b, cfg.n_heads * qlen, 1, hd), xkc, xvc,
            jnp.asarray(cfg.enc_seq, jnp.int32),
        ).reshape(b, cfg.n_heads, qlen, hd)
        xc = xc + nn.dense(dense._merge_heads(xo), p["xwo"])
        xc = xc + dense._mlp(nn.rms_norm(xc, p["ln2"]), p, cfg)
        return xc, (kc, vc)

    L = cfg.n_layers
    ks_in = cache.get("kscale", jnp.zeros((L, 1), jnp.float32))
    vs_in = cache.get("vscale", jnp.zeros((L, 1), jnp.float32))
    x, (ks, vs) = jax.lax.scan(
        body, x, (params["dec_stack"], cache["k"], cache["v"],
                  ks_in, vs_in, cache["xk"], cache["xv"]))
    x = nn.rms_norm(x, params["final_norm"])
    return nn.unembed(x, params["unembed"]), dict(cache, k=ks, v=vs)


def decode_step(params, cache, tokens, cfg: ModelConfig, *, qparams=None,
                embeds=None):
    """One dense-arena decode step. Under ``serve_quant`` the self-attention
    K/V are requantized at write time and attended through the ITA integer
    pipeline — the dense int8 reference the paged int8 pool must match
    token-for-token. Cross-attention stays float."""
    from repro.models.cache import quantize_kv

    x = nn.embed(tokens[:, None], params["embed"], cfg.compute_dtype)
    b = x.shape[0]
    pos = dense._as_positions(cache["len"], b)
    hd = cfg.hd

    def body(xc, slices):
        p, kc, vc, xkc, xvc = slices
        h = nn.rms_norm(xc, p["ln1"])
        q = nn.dense(h, p["wq"]).reshape(b, 1, cfg.n_heads, hd).transpose(0, 2, 1, 3)
        k = nn.dense(h, p["wk"]).reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
        v = nn.dense(h, p["wv"]).reshape(b, 1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
        q = nn.rope(q, pos[:, None, None], cfg.rope_theta)  # per-row positions
        k = nn.rope(k, pos[:, None, None], cfg.rope_theta)
        if cfg.serve_quant:
            k, v = quantize_kv(k, attn.KV_SCALE), quantize_kv(v, attn.KV_SCALE)
        sc = dense._cache_write({"k": kc, "v": vc}, k, v, pos, "G", cfg)
        kc, vc = sc["k"], sc["v"]
        if cfg.serve_quant:
            o = attn.decode_attention_int8(q, kc, vc, pos + 1, cfg)
        else:
            o = attn.decode_attention(q, kc, vc, pos + 1)
        xc = xc + nn.dense(dense._merge_heads(o), p["wo"])
        # cross attention against cached encoder K/V (always full enc_seq)
        hx = nn.rms_norm(xc, p["lnx"])
        xq = nn.dense(hx, p["xwq"]).reshape(b, 1, cfg.n_heads, hd).transpose(0, 2, 1, 3)
        xo = attn.decode_attention(xq, xkc, xvc, jnp.asarray(cfg.enc_seq, jnp.int32))
        xc = xc + nn.dense(dense._merge_heads(xo), p["xwo"])
        xc = xc + dense._mlp(nn.rms_norm(xc, p["ln2"]), p, cfg)
        return xc, (kc, vc)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["dec_stack"], cache["k"], cache["v"],
                  cache["xk"], cache["xv"]))
    x = nn.rms_norm(x, params["final_norm"])
    logits = nn.unembed(x, params["unembed"])
    return logits[:, 0], dict(cache, k=ks, v=vs, len=cache["len"] + 1)
