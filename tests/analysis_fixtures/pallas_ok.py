"""pallas-index near misses: kernel idioms that must NOT flag.

Covers: ``pl.ds`` dynamic ref stores (the RG-LRU kernel's form), constant-index
stores, dynamic *reads* of scalar-prefetch refs (the paged-attention
idiom), and matching BlockSpec arity.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel_fixed(loga_ref, u_ref, o_ref, h_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    log_a = loga_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)
    a = jnp.exp(log_a)
    beta = jnp.sqrt(-jnp.expm1(2.0 * log_a))
    bu = beta * u

    def step(t, h):
        h = a[t] * h + bu[t]
        # the dynamic position rides pl.ds in a ref store
        o_ref[pl.ds(0, 1), pl.ds(t, 1), :] = h[None, None].astype(
            o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def rglru_fixed(log_a, u, *, chunk=256, interpret=False):
    bsz, s, d = u.shape
    kernel = functools.partial(_rglru_kernel_fixed, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bsz, s // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda b, ci: (b, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d), lambda b, ci: (b, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, d), u.dtype),
        scratch_shapes=[pltpu.VMEM((d,), jnp.float32)],
        interpret=interpret,
    )(log_a, u)


def _attend_kernel(lens_ref, start_ref, q_ref, o_ref):
    b = pl.program_id(0)
    # dynamic *reads* of scalar-prefetch refs are the paged idiom
    length = lens_ref[b]
    first = start_ref[b]
    q = q_ref[0, 0]
    # constant-index stores are static
    o_ref[0, 0] = q * jnp.float32(length - first)


def dispatch_prefetch(lens, start, q):
    b, d = q.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, d), lambda i, lens, start: (i, 0))],
        out_specs=pl.BlockSpec((1, d), lambda i, lens, start: (i, 0)),
    )
    return pl.pallas_call(
        _attend_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), q.dtype),
    )(lens, start, q)
