"""Pallas TPU kernel: RG-LRU (Real-Gated Linear Recurrent Unit) scan.

The recurrence of Griffin / RecurrentGemma (arXiv:2402.19427):

    a_t = exp(log_a_t)                     (log_a_t = −c·softplus(Λ)·r_t ≤ 0)
    h_t = a_t ⊙ h_{t−1} + √(1 − a_t²) ⊙ (i_t ⊙ x_t)

The kernel receives the precomputed ``log_a`` and the gated input
``u = i ⊙ x`` (gates are plain GEMMs handled by the int8 GEMM path) and
runs the diagonal recurrence chunk-by-chunk: grid (batch, chunks) with the
hidden state carried in VMEM scratch; within a chunk a ``fori_loop`` of
width-D vector ops runs on the VPU (the op is memory-bound — one FMA per
element — so VPU throughput suffices; MXU has no role in a diagonal
recurrence).

√(1 − a²) is computed as ``sqrt(−expm1(2·log_a))`` for stability as a→1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(loga_ref, u_ref, o_ref, h_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    log_a = loga_ref[0].astype(jnp.float32)   # [L, D]
    u = u_ref[0].astype(jnp.float32)          # [L, D]
    a = jnp.exp(log_a)
    beta = jnp.sqrt(-jnp.expm1(2.0 * log_a))  # √(1 − a²), stable
    bu = beta * u

    def step(t, h):
        h = a[t] * h + bu[t]
        o_ref[pl.ds(0, 1), pl.ds(t, 1), :] = h[None, None].astype(
            o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rglru_pallas(
    log_a: jax.Array,  # [B, S, D] f32/bf16, ≤ 0
    u: jax.Array,      # [B, S, D] gated input i⊙x
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    bsz, s, d = u.shape
    if s % chunk:
        raise ValueError(f"seq {s} not divisible by chunk {chunk}")
    grid = (bsz, s // chunk)
    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda b, ci: (b, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d), lambda b, ci: (b, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, d), u.dtype),
        scratch_shapes=[pltpu.VMEM((d,), jnp.float32)],
        interpret=interpret,
    )(log_a, u)
