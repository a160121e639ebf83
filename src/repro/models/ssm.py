"""Mamba-2 (SSD) family — attention-free, the ``mamba2-2.7b`` assignment.

Block: RMSNorm → in_proj → [z | xBC | dt] → causal conv1d(4) on xBC → SiLU →
SSD scan (kernels/ssd_scan) → gated RMSNorm(y)·SiLU(z) → out_proj.

The paper's ITA attention technique is **inapplicable** here (attention-
free; DESIGN.md §5); the INT8 GEMM path still applies to the projections.
Decode carries O(1) state (conv tail + [H, N, P] SSD state) — which is why
this arch runs the ``long_500k`` cell.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_decode_step
from repro.models import layers as nn
from repro.models.config import ModelConfig
from repro.models.schema import TensorSpec
from repro.parallel import context as pctx


def _dims(cfg: ModelConfig):
    d_inner = cfg.expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_headdim
    conv_ch = d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
    return d_inner, n_heads, conv_ch


def _layer_schema(cfg: ModelConfig, n_stack: int) -> Dict[str, TensorSpec]:
    d = cfg.d_model
    d_inner, h, conv_ch = _dims(cfg)
    L = ("layers",)

    def t(shape, axes, **kw):
        return TensorSpec((n_stack, *shape), L + axes, **kw)

    return {
        "ln": t((d,), ("embed",), init="zeros"),
        "w_in": t((d, 2 * d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state + h),
                  ("embed", "mlp")),
        "conv_w": t((cfg.d_conv, conv_ch), (None, "mlp"), scale=0.5),
        "conv_b": t((conv_ch,), ("mlp",), init="zeros"),
        "a_log": t((h,), ("heads",), init="ones"),
        "dt_bias": t((h,), ("heads",), init="zeros"),
        "d_skip": t((h,), ("heads",), init="ones"),
        "norm_g": t((d_inner,), ("mlp",), init="zeros"),
        "w_out": t((d_inner, d), ("mlp", "embed")),
    }


def schema(cfg: ModelConfig):
    return {
        "embed": TensorSpec((cfg.vocab, cfg.d_model), ("vocab", "embed_io"),
                            init="embed"),
        "final_norm": TensorSpec((cfg.d_model,), ("embed",), init="zeros"),
        "stacks": [_layer_schema(cfg, cfg.n_layers)],
        "unembed": TensorSpec((cfg.vocab, cfg.d_model), ("vocab", "embed_io")),
    }


def _split_proj(zxbcdt, cfg: ModelConfig):
    d_inner, h, _ = _dims(cfg)
    gn = cfg.ssm_ngroups * cfg.ssm_state
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    return z, xbc, dt  # [..., d_inner], [..., d_inner+2GN], [..., H]


def _conv1d(xbc, w, b):
    """Causal depthwise conv over time. xbc [B, S, C]; w [K, C]."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + xbc.shape[1]] * w[i][None, None, :] for i in range(k)
    )
    return out + b[None, None, :]


def _ssd_block(x, p, cfg: ModelConfig, backend: Optional[str] = None,
               return_state: bool = False):
    """[B, S, D] → [B, S, D] through one SSD mixing block."""
    b, s, _ = x.shape
    d_inner, h, _ = _dims(cfg)
    n, g, pdim = cfg.ssm_state, cfg.ssm_ngroups, cfg.ssm_headdim

    zxbcdt = pctx.constrain(nn.dense(x, p["w_in"]), ("batch", None, "mlp"))
    z, xbc_raw, dt = _split_proj(zxbcdt, cfg)
    xbc = jax.nn.silu(_conv1d(xbc_raw, p["conv_w"].astype(x.dtype),
                              p["conv_b"].astype(x.dtype)).astype(jnp.float32)).astype(x.dtype)
    xs, bmat, cmat = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))          # [H], negative
    dta = (dt * a).transpose(0, 2, 1)                      # [B, H, S]
    xh = xs.reshape(b, s, h, pdim).transpose(0, 2, 1, 3)   # [B, H, S, P]
    xh = xh * dt.transpose(0, 2, 1)[..., None].astype(xh.dtype)
    bm = bmat.reshape(b, s, g, n).transpose(0, 2, 1, 3)    # [B, G, S, N]
    cm = cmat.reshape(b, s, g, n).transpose(0, 2, 1, 3)

    scan_out = ssd_scan(dta, xh.astype(jnp.float32), bm.astype(jnp.float32),
                        cm.astype(jnp.float32), backend=backend,
                        return_state=return_state)  # [B, H, S, P]
    y, ssd_state = scan_out if return_state else (scan_out, None)
    y = y + p["d_skip"].astype(jnp.float32)[None, :, None, None] * xh.astype(jnp.float32)
    y = y.transpose(0, 2, 1, 3).reshape(b, s, d_inner).astype(x.dtype)
    y = nn.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                    p["norm_g"])
    out = pctx.constrain(nn.dense(y, p["w_out"]), ("batch", None, None))
    if return_state:
        k = cfg.d_conv - 1
        conv_tail = xbc_raw[:, -k:].astype(cfg.compute_dtype)  # [B, K-1, C]
        return out, (conv_tail, ssd_state)
    return out


def forward(params, tokens, cfg: ModelConfig, *, embeds=None):
    x = embeds if embeds is not None else nn.embed(
        tokens, params["embed"], cfg.compute_dtype)

    def apply_layer(xc, p):
        return xc + _ssd_block(nn.rms_norm(xc, p["ln"]), p, cfg)

    if cfg.remat:
        apply_layer = jax.checkpoint(apply_layer)

    def body(xc, p):
        return apply_layer(xc, p), None

    x, _ = jax.lax.scan(body, x, params["stacks"][0])
    x = nn.rms_norm(x, params["final_norm"])
    return nn.unembed(x, params["unembed"])


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               quantized=None):
    """O(1) decode state: conv tail + SSD state, stacked over layers."""
    d_inner, h, conv_ch = _dims(cfg)
    return {
        "conv": jnp.zeros((cfg.n_layers, batch, cfg.d_conv - 1, conv_ch),
                          cfg.compute_dtype),
        "ssd": jnp.zeros((cfg.n_layers, batch, h, cfg.ssm_state,
                          cfg.ssm_headdim), jnp.float32),
        "len": jnp.zeros((batch,), jnp.int32),  # per-row position vector
    }


def decode_step(params, cache, tokens, cfg: ModelConfig, *, qparams=None,
                embeds=None):
    x = embeds if embeds is not None else nn.embed(
        tokens[:, None], params["embed"], cfg.compute_dtype)
    b = x.shape[0]
    d_inner, h, conv_ch = _dims(cfg)
    n, g, pdim = cfg.ssm_state, cfg.ssm_ngroups, cfg.ssm_headdim

    def body(xc, slices):
        p, conv_c, ssd_c = slices
        hx = nn.rms_norm(xc, p["ln"])
        zxbcdt = nn.dense(hx, p["w_in"])
        z, xbc, dt = _split_proj(zxbcdt, cfg)          # [B, 1, ·]
        hist = jnp.concatenate([conv_c, xbc], axis=1)  # [B, K, C]
        w = p["conv_w"].astype(xc.dtype)
        conv_out = jnp.einsum("bkc,kc->bc", hist, w) + p["conv_b"].astype(xc.dtype)
        conv_new = hist[:, 1:]
        xbc1 = jax.nn.silu(conv_out.astype(jnp.float32)).astype(xc.dtype)
        xs, bm, cm = jnp.split(xbc1, [d_inner, d_inner + g * n], axis=-1)
        dtf = jax.nn.softplus(
            dt[:, 0].astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["a_log"].astype(jnp.float32))
        dta_t = dtf * a                                   # [B, H]
        xh = xs.reshape(b, h, pdim) * dtf[..., None].astype(xs.dtype)
        bm_h = jnp.repeat(bm.reshape(b, g, n), h // g, axis=1)
        cm_h = jnp.repeat(cm.reshape(b, g, n), h // g, axis=1)
        ssd_new, y = ssd_decode_step(
            ssd_c, dta_t, xh.astype(jnp.float32), bm_h.astype(jnp.float32),
            cm_h.astype(jnp.float32))
        y = y + p["d_skip"].astype(jnp.float32)[None, :, None] * xh.astype(jnp.float32)
        y = y.reshape(b, 1, d_inner).astype(xc.dtype)
        y = nn.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(xc.dtype),
                        p["norm_g"])
        xc = xc + nn.dense(y, p["w_out"])
        return xc, (conv_new, ssd_new)

    x, (conv_new, ssd_new) = jax.lax.scan(
        body, x, (params["stacks"][0], cache["conv"], cache["ssd"]))
    x = nn.rms_norm(x, params["final_norm"])
    logits = nn.unembed(x, params["unembed"])
    return logits[:, 0], {"conv": conv_new, "ssd": ssd_new,
                          "len": cache["len"] + 1}


def prefill(params, tokens, cfg: ModelConfig, max_len: int, *, embeds=None):
    """Prefill: forward pass capturing the exact per-layer (conv, SSD) state."""
    x = embeds if embeds is not None else nn.embed(
        tokens, params["embed"], cfg.compute_dtype)
    b, s = x.shape[:2]

    def body(xc, p):
        out, state = _ssd_block(nn.rms_norm(xc, p["ln"]), p, cfg,
                                return_state=True)
        return xc + out, state

    x, (conv_states, ssd_states) = jax.lax.scan(body, x, params["stacks"][0])
    x = nn.rms_norm(x, params["final_norm"])
    logits = nn.unembed(x[:, -1:], params["unembed"])
    cache = {"conv": conv_states, "ssd": ssd_states,
             "len": jnp.full((b,), s, jnp.int32)}
    return logits[:, 0], cache
