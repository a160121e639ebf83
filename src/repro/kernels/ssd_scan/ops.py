"""Public op: SSD chunked scan with backend dispatch.

The ``xla`` backend uses the same chunked matmul-form algorithm expressed in
jnp over a ``lax.scan`` of chunks — structurally identical collectives and
FLOPs to the Pallas kernel, so the dry-run roofline is representative.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_backend
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.ssd_scan.ref import ssd_decode_step, ssd_scan_ref  # noqa: F401


@functools.partial(jax.jit, static_argnames=("chunk", "return_state"))
def _ssd_chunked_xla(dta, x, b_mat, c_mat, *, chunk: int = 128,
                     return_state: bool = False):
    bsz, h, s, p = x.shape
    _, g, _, n = b_mat.shape
    hpg = h // g
    nc = s // chunk

    # [B,H,NC,L,...] chunk views
    dta_c = dta.reshape(bsz, h, nc, chunk).astype(jnp.float32)
    x_c = x.reshape(bsz, h, nc, chunk, p).astype(jnp.float32)
    b_c = b_mat.reshape(bsz, g, nc, chunk, n).astype(jnp.float32)
    c_c = c_mat.reshape(bsz, g, nc, chunk, n).astype(jnp.float32)
    # broadcast groups→heads lazily per chunk inside the scan body
    idx = jnp.arange(h) // hpg

    s_a = jnp.cumsum(dta_c, axis=-1)  # [B,H,NC,L]
    rows = jnp.arange(chunk)[:, None]
    cols = jnp.arange(chunk)[None, :]
    causal = cols <= rows

    def chunk_body(state, ci):
        dta_i = s_a[:, :, ci]                    # [B,H,L]
        x_i = x_c[:, :, ci]                      # [B,H,L,P]
        b_i = b_c[:, idx, ci]                    # [B,H,L,N]
        c_i = c_c[:, idx, ci]                    # [B,H,L,N]
        delta = dta_i[..., :, None] - dta_i[..., None, :]
        ldec = jnp.where(causal, jnp.exp(delta), 0.0)
        scores = jnp.einsum("bhln,bhmn->bhlm", c_i, b_i) * ldec
        y_intra = jnp.einsum("bhlm,bhmp->bhlp", scores, x_i)
        y_inter = jnp.exp(dta_i)[..., None] * jnp.einsum(
            "bhln,bhnp->bhlp", c_i, state
        )
        s_last = dta_i[..., -1]
        w = jnp.exp(s_last[..., None] - dta_i)   # [B,H,L]
        state = jnp.exp(s_last)[..., None, None] * state + jnp.einsum(
            "bhln,bhlp->bhnp", b_i * w[..., None], x_i
        )
        return state, y_intra + y_inter

    state0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    state_f, ys = jax.lax.scan(chunk_body, state0, jnp.arange(nc))
    y = jnp.moveaxis(ys, 0, 2)  # [B,H,NC,L,P]
    y = y.reshape(bsz, h, s, p).astype(x.dtype)
    return (y, state_f) if return_state else y


def ssd_scan(
    dta: jax.Array,
    x: jax.Array,
    b_mat: jax.Array,
    c_mat: jax.Array,
    *,
    chunk: int = 128,
    backend: Optional[str] = None,
    return_state: bool = False,
):
    backend = resolve_backend(backend)
    chunk = min(chunk, x.shape[2])
    s = x.shape[2]
    pad = (-s) % chunk
    if pad and backend == "xla":
        # zero-pad the tail: Δ·A=0 → decay 1, B=0 → state untouched; the
        # padded outputs are discarded below.
        pz = lambda a: jnp.pad(a, [(0, 0)] * 2 + [(0, pad)] + [(0, 0)] * (a.ndim - 3))
        dta = jnp.pad(dta, ((0, 0), (0, 0), (0, pad)))
        x, b_mat, c_mat = pz(x), pz(b_mat), pz(c_mat)
        out = ssd_scan(dta, x, b_mat, c_mat, chunk=chunk, backend=backend,
                       return_state=return_state)
        if return_state:
            return out[0][:, :, :s], out[1]
        return out[:, :, :s]
    if backend in ("pallas", "interpret"):
        if return_state:
            raise NotImplementedError("state capture: use backend='xla'")
        return ssd_scan_pallas(
            dta, x, b_mat, c_mat, chunk=chunk, interpret=backend == "interpret"
        )
    if backend == "xla":
        return _ssd_chunked_xla(dta, x, b_mat, c_mat, chunk=chunk,
                                return_state=return_state)
    raise ValueError(f"unknown backend {backend!r}")
