"""Public op: RG-LRU scan with backend dispatch.

The ``xla`` backend uses an associative scan (log-depth) — the form XLA
lowers to efficient fused loops and that shards cleanly for the dry-run.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_backend
from repro.kernels.rglru.kernel import rglru_pallas
from repro.kernels.rglru.ref import rglru_decode_step, rglru_ref  # noqa: F401


@jax.jit
def _rglru_xla(log_a: jax.Array, u: jax.Array) -> jax.Array:
    """Associative-scan form: h_t = a_t h_{t−1} + b_t as pairs (a, b)."""
    a = jnp.exp(log_a.astype(jnp.float32))
    beta = jnp.sqrt(-jnp.expm1(2.0 * log_a.astype(jnp.float32)))
    bu = beta * u.astype(jnp.float32)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    _, hs = jax.lax.associative_scan(combine, (a, bu), axis=1)
    return hs.astype(u.dtype)


def rglru(
    log_a: jax.Array,
    u: jax.Array,
    *,
    chunk: int = 256,
    backend: Optional[str] = None,
) -> jax.Array:
    backend = resolve_backend(backend)
    if backend in ("pallas", "interpret"):
        return rglru_pallas(log_a, u, chunk=chunk,
                            interpret=backend == "interpret")
    if backend == "xla":
        return _rglru_xla(log_a, u)
    raise ValueError(f"unknown backend {backend!r}")
