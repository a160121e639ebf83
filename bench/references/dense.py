"""Plain float32 reference of a Phi-3 style dense decoder.

Follows the published model (arXiv:2404.14219, Hugging Face ``Phi3``):
token embedding; per layer, pre-RMSNorm attention with rotate-half RoPE
and grouped K/V heads, then pre-RMSNorm SwiGLU MLP, each added to the
residual; a final RMSNorm and an untied output head. Every matrix product
runs in float32 at ``precision="highest"``. It imports nothing of the
program under test; the weights are the benchmark's own (``weights.py``),
kept in their served dtype and widened to float32 one layer at a time so
the reference fits beside them. A norm leaf holds the gain's offset from
1 (``weights.py``).

``logits_at`` runs one sequence, teacher-forced, and returns the logits
at the positions asked for. Attention runs in blocks of query rows, so a
12k-token document never holds a whole score matrix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256


def _mm(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision=HIGHEST)


def _rms_norm(x, offset, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + offset.astype(jnp.float32))


def _rope(x, theta):
    """x: [S, H, D] at positions 0..S-1, rotate-half convention."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv     # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal attention; q [S, Hq, D], k/v [S, Hkv, D] -> [S, Hq, D]."""
    s, hq, d = q.shape
    group = hq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    nblk = s // Q_BLOCK
    cols = jnp.arange(s)

    def block(i):
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * d ** -0.5
        sc = jnp.where(cols[None, None, :] <= rows[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(nblk))      # [nblk, QB, Hq, D]
    return out.reshape(s, hq, d)


@functools.partial(jax.jit, static_argnames=("m",))
def _logits(params, tokens, pos, m):
    """tokens [S] (S a multiple of Q_BLOCK, right-padded), pos [P]."""
    m = dict(m)
    hd, eps = m["head_dim"], m["rms_norm_eps"]
    s = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        h = _rms_norm(x, p["ln1"], eps)
        q = _rope(_mm(h, p["wq"]).reshape(s, m["n_heads"], hd),
                  m["rope_theta"])
        k = _rope(_mm(h, p["wk"]).reshape(s, m["n_kv_heads"], hd),
                  m["rope_theta"])
        v = _mm(h, p["wv"]).reshape(s, m["n_kv_heads"], hd)
        x = x + _mm(_attention(q, k, v).reshape(s, -1), p["wo"])
        h = _rms_norm(x, p["ln2"], eps)
        x = x + _mm(jax.nn.silu(_mm(h, p["wg"])) * _mm(h, p["wu"]), p["wd"])
        return x, None

    x, _ = jax.lax.scan(layer, x, params["stacks"][0])
    x = _rms_norm(x[pos], params["final_norm"], eps)
    return _mm(x, params["unembed"].T)


def logits_at(params, tokens, positions, m: dict):
    """Float32 logits [len(positions), vocab] of one teacher-forced
    sequence. ``m`` holds the model's sizes (``d_model``, ``n_heads``,
    ``n_kv_heads``, ``head_dim``, ``rope_theta``, ``rms_norm_eps``...)."""
    import numpy as np

    n = len(tokens)
    width = -(-n // Q_BLOCK) * Q_BLOCK
    padded = np.zeros((width,), np.int32)
    padded[:n] = tokens
    key = tuple(sorted((k, v) for k, v in m.items()
                       if isinstance(v, (int, float, str))))
    return _logits(params, jnp.asarray(padded),
                   jnp.asarray(np.asarray(positions, np.int32)), key)
