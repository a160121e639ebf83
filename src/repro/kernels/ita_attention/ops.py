"""Public op: INT8 fused attention (the paper's technique) with GQA support.

``ita_attention`` accepts [B, H, S, D] int8 tensors with separate query and
KV head counts (GQA: kv heads are shared by h_q // h_kv query heads) and
dispatches to the Pallas kernel (``pallas``/``interpret``) or the
structurally identical XLA path (``xla`` — used by the dry-run and the
serving engine on CPU).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_backend
from repro.kernels.ita_attention.kernel import ita_attention_pallas
from repro.kernels.ita_attention.ref import ita_attention_ref


def ita_attention(
    q: jax.Array,  # [B, Hq, Sq, D] int8
    k: jax.Array,  # [B, Hkv, Skv, D] int8
    v: jax.Array,  # [B, Hkv, Skv, D] int8
    *,
    qk_scale: float,
    v_scale: float,
    out_scale: float,
    causal: bool = False,
    logit_amax: float = 10.0,
    block_q: int = 128,
    block_kv: int = 128,
    backend: Optional[str] = None,
) -> jax.Array:
    backend = resolve_backend(backend)
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    group = hq // hkv
    if group > 1:  # GQA: expand kv heads to query heads (logical broadcast)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hq, skv, d)
    vf = v.reshape(b * hq, skv, d)

    kwargs = dict(
        qk_scale=qk_scale, v_scale=v_scale, out_scale=out_scale,
        causal=causal, logit_amax=logit_amax,
    )
    if backend in ("pallas", "interpret"):
        y = ita_attention_pallas(
            qf, kf, vf, block_q=block_q, block_kv=block_kv,
            interpret=backend == "interpret", **kwargs,
        )
    elif backend == "xla":
        y = ita_attention_ref(qf, kf, vf, block_kv=block_kv, **kwargs)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return y.reshape(b, hq, sq, d)
