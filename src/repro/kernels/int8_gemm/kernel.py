"""Pallas TPU kernel: W8A8 GEMM with int32 accumulation + fused requant.

The TPU-native realization of the CHIMERA TAC PE array:

  * the 16-PE × 64-wide weight-stationary tile becomes an MXU-aligned
    (bm × bk)·(bk × bn) block matmul, int8×int8→int32;
  * the 2 KiB double-buffered weight memory becomes the Pallas grid
    pipeline — BlockSpec streaming HBM→VMEM is double-buffered by
    construction, so weight-tile fetch overlaps compute exactly like the
    TAC's shadow buffer;
  * the requantization + activation epilogue (the TAC's requant block and
    per-PE activation unit) is fused on the last K step, so the int32
    accumulator never leaves VMEM.

Block shapes default to the paper-faithful proportions (small output tile,
long contraction axis — the TAC is 16×64) padded to MXU alignment; the
beyond-paper configuration retunes them for VMEM occupancy (see
EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ita, quant

# Paper-faithful block shape: mirrors the TAC 16(out)×64(in) aspect ratio,
# padded to MXU/VREG alignment (8×128 lanes; MXU 128×128).
PAPER_BLOCK = (256, 512, 128)  # (bm, bk, bn)
# Beyond-paper tuned block (see §Perf): square-ish tiles maximize MXU
# utilization and VMEM reuse on v5e.
TUNED_BLOCK = (512, 512, 512)
# int8 second-minor tile: a row block is a multiple of this
INT8_ROW_TILE = 32


def _gemm_kernel(x_ref, w_ref, b_ref, m_ref, s_ref, o_ref, acc_ref,
                 *, nk: int, activation: str, act_scales):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...] + b_ref[...]  # int32 bias, broadcast [1, bn]
        if activation == "relu":
            acc = ita.int_relu(acc)  # exact on the int32 accumulator
        y = quant.requantize(acc, m_ref[...], s_ref[...])
        if activation == "gelu":
            in_scale, out_scale = act_scales
            y = ita.int_gelu_i8(y.astype(jnp.int32), in_scale, out_scale)
        o_ref[...] = y


@functools.partial(
    jax.jit,
    static_argnames=("block", "activation", "act_scales", "interpret"),
)
def int8_gemm_pallas(
    x_q: jax.Array,       # [M, K] int8
    w_q: jax.Array,       # [K, N] int8
    bias: jax.Array,      # [N] int32
    mult: jax.Array,      # [N] int32 fixed-point requant multiplier
    shift: jax.Array,     # [N] int32 requant shift
    *,
    block=PAPER_BLOCK,
    activation: str = "none",
    act_scales: Optional[tuple] = None,
    interpret: bool = False,
) -> jax.Array:
    """Blocked W8A8 GEMM → int8, requant fused.

    K and N must divide their blocks. Any M is served: the row block is
    fitted to M (rounded up to the int8 sublane tile of 32) and the rows
    are zero-padded to a multiple of it, so a decode batch of a few slots
    runs one small row block instead of a 256-row one."""
    m_dim, k_dim = x_q.shape
    _, n_dim = w_q.shape
    bm, bk, bn = block
    bm = min(bm, -(-m_dim // INT8_ROW_TILE) * INT8_ROW_TILE)
    bk, bn = min(bk, k_dim), min(bn, n_dim)
    if k_dim % bk or n_dim % bn:
        raise ValueError(f"shapes {(m_dim, k_dim, n_dim)} not divisible by block {(bm, bk, bn)}")
    m_pad = -(-m_dim // bm) * bm
    if m_pad != m_dim:
        x_q = jnp.pad(x_q, ((0, m_pad - m_dim), (0, 0)))
    nk = k_dim // bk
    grid = (m_pad // bm, n_dim // bn, nk)

    kernel = functools.partial(
        _gemm_kernel, nk=nk, activation=activation, act_scales=act_scales
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_dim), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(
        x_q,
        w_q,
        bias.reshape(1, n_dim),
        mult.reshape(1, n_dim),
        shift.reshape(1, n_dim),
    )[:m_dim]
