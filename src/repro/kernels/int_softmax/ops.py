"""Public op: integer softmax with backend dispatch."""

from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import resolve_backend

from repro.kernels.int_softmax.kernel import int_softmax_pallas
from repro.kernels.int_softmax.ref import int_softmax_ref


def int_softmax(
    logits_q: jax.Array,  # [..., C] int8
    *,
    logit_scale: float,
    backend: Optional[str] = None,
) -> jax.Array:
    backend = resolve_backend(backend)
    lead = logits_q.shape[:-1]
    x2 = logits_q.reshape(-1, logits_q.shape[-1])
    if backend in ("pallas", "interpret"):
        y = int_softmax_pallas(
            x2, logit_scale=logit_scale, interpret=backend == "interpret"
        )
    elif backend == "xla":
        y = int_softmax_ref(x2, logit_scale=logit_scale)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return y.reshape(*lead, -1)
