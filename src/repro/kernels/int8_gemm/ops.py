"""Public op: quantized linear layer with backend dispatch.

Backends:
  * ``pallas``    — the TPU kernel (real hardware).
  * ``interpret`` — the same kernel body interpreted on CPU (tests).
  * ``xla``       — structurally identical math through XLA ops; used for
                    the multi-pod dry-run (Pallas TPU kernels cannot lower
                    on the CPU backend) and as a portable fallback.

All three share the integer contract from ``repro.core.quant`` and agree
bit-exactly (asserted in tests/test_int8_gemm.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels import resolve_backend
from repro.kernels.int8_gemm.kernel import PAPER_BLOCK, int8_gemm_pallas
from repro.kernels.int8_gemm.ref import int8_gemm_ref


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantizedLinearParams:
    """Static-quantized weights + requant constants for one linear layer."""

    w_q: jax.Array      # [K, N] int8
    bias: jax.Array     # [N] int32 (bias folded to accumulator scale)
    mult: jax.Array     # [N] int32
    shift: jax.Array    # [N] int32

    @classmethod
    def from_float(cls, w, bias_f, in_scale: float, out_scale: float):
        w_q, w_scale = quant.quantize_weights(w)          # per-out-channel
        acc_scale = w_scale * in_scale                    # int32 acc scale
        bias_q = jnp.round(bias_f / acc_scale).astype(jnp.int32)
        mult, shift = quant.quantize_to_fixed_point(acc_scale / out_scale)
        return cls(w_q=w_q, bias=bias_q, mult=mult, shift=shift)


def int8_gemm(
    x_q: jax.Array,
    params: QuantizedLinearParams,
    *,
    activation: str = "none",
    act_scales: Optional[tuple] = None,
    backend: Optional[str] = None,
    block=PAPER_BLOCK,
) -> jax.Array:
    """[..., K] int8 → [..., N] int8 quantized linear."""
    backend = resolve_backend(backend)
    lead = x_q.shape[:-1]
    x2 = x_q.reshape(-1, x_q.shape[-1])
    if backend in ("pallas", "interpret"):
        y = int8_gemm_pallas(
            x2, params.w_q, params.bias, params.mult, params.shift,
            block=block, activation=activation, act_scales=act_scales,
            interpret=backend == "interpret",
        )
    elif backend == "xla":
        y = int8_gemm_ref(
            x2, params.w_q, params.bias, params.mult, params.shift,
            activation=activation, act_scales=act_scales,
        )
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return y.reshape(*lead, -1)
