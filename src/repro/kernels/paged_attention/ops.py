"""Public ops: paged decode attention (float + int8 pools) with dispatch.

``paged_attention(q, k_pool, v_pool, block_table, lens)`` computes one-token
decode attention where each batch row's KV lives in fixed-size blocks of a
shared pool, addressed through a per-row block table (position ``p`` is
table entry ``(p - start) // block_len``, offset ``p % block_len``).
``start`` (default zeros) is the absolute position of table entry 0: ring
tables for sliding-window layers rotate and hand the kernel the window's
block-aligned start per row; full-history tables leave it at 0.

``paged_attention_int8`` is the quantized-residency variant: the pools are
int8 blocks with per-block scales (the serving layout fills the scales with
the static ``attn.KV_SCALE`` calibration; the arrays exist so per-block
calibration can land without a layout change).

Backends (``backend=None`` picks from the platform: ``pallas`` on a TPU,
``xla`` elsewhere — see ``repro.kernels.resolve_backend``):
  * ``pallas``    — TPU kernel; scalar-prefetched block table (plus, for
    int8, the per-block scale vectors) drives the BlockSpec index maps so
    pool blocks are DMA'd on demand. Int8 pools move half the bytes and
    dequantize on the fly into f32 flash accumulators.
  * ``interpret`` — same kernels through the Pallas interpreter (CPU/CI).
  * ``xla``       — gather-then-dense oracle (``ref.py``); the default off
    the TPU. For int8 pools this is the ITA integer pipeline over
    the gathered blocks — bit-identical to the dense int8 serving
    reference, which is what the paged-vs-dense token-identity matrix
    anchors on.

Note the int8 numerics split: ``xla`` is the ITA integer softmax (exact,
token-identity anchor); ``pallas``/``interpret`` run the fused kernel whose
softmax is f32 flash over the same exact integer score dots (contract:
``ref.paged_attention_int8_dequant_ref``). ``INT8_BACKENDS`` names the
backends that implement int8 blocks at all — engines validate against it
at config time so a quantized arch on an unsupported backend fails at
construction, not mid-serve inside a jitted step.

**Mesh-sharded serving**: every backend here is *rank-local* — inside a
``shard_map``'d decode step each rank calls these ops on its local pool
shard (a KV-head slice in "heads" mode, a block slice plus local table in
"blocks" mode) and the serving layer handles the one collective per layer
(output all-gather / owner-masked psum). The ops themselves contain no
collectives and are shape-generic over the sharded extents;
``ref.paged_attention_sharded_oracle`` is the head-sharded harness that
pins the bit-identity of this arrangement.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.annotations import hot_path
from repro.kernels import resolve_backend
from repro.kernels.paged_attention.kernel import (
    paged_attention_int8_pallas, paged_attention_pallas,
    paged_attention_verify_int8_pallas, paged_attention_verify_pallas,
)
from repro.kernels.paged_attention.ref import (
    paged_attention_int8_ref, paged_attention_ref,
    paged_attention_verify_int8_ref, paged_attention_verify_ref,
)

# backends implementing decode over float block pools
BACKENDS = ("pallas", "interpret", "xla")
# backends implementing decode over int8 block pools (+ per-block scales)
INT8_BACKENDS = ("pallas", "interpret", "xla")


@hot_path
def paged_attention(
    q: jax.Array,            # [B, Hq, 1, D] float (post-RoPE)
    k_pool: jax.Array,       # [N, Hkv, block_len, D]
    v_pool: jax.Array,       # [N, Hkv, block_len, D]
    block_table: jax.Array,  # [B, M] int32 pool indices
    lens: jax.Array,         # [B] int32 valid positions per row
    *,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,  # [B] int32 abs position of entry 0
    backend: Optional[str] = None,
) -> jax.Array:
    backend = resolve_backend(backend)
    if q.shape[1] % k_pool.shape[1]:
        raise ValueError(
            f"query heads {q.shape[1]} not a multiple of kv heads "
            f"{k_pool.shape[1]}")
    if backend in ("pallas", "interpret"):
        return paged_attention_pallas(
            q, k_pool, v_pool, block_table, lens, window=window, start=start,
            interpret=backend == "interpret")
    if backend == "xla":
        return paged_attention_ref(
            q, k_pool, v_pool, block_table, lens, window=window, start=start)
    raise ValueError(f"unknown backend {backend!r}")


@hot_path
def paged_attention_int8(
    q: jax.Array,            # [B, Hq, 1, D] float (post-RoPE)
    k_pool: jax.Array,       # [N, Hkv, block_len, D] int8
    v_pool: jax.Array,       # [N, Hkv, block_len, D] int8
    block_table: jax.Array,  # [B, M] int32 pool indices
    lens: jax.Array,         # [B] int32 valid positions per row
    *,
    k_scale: Optional[jax.Array] = None,  # [N] f32 per-block (None→KV_SCALE)
    v_scale: Optional[jax.Array] = None,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,  # [B] int32 abs position of entry 0
    backend: Optional[str] = None,
) -> jax.Array:
    """Decode attention over int8 block pools (see module docstring).

    The ``xla`` (ITA) backend compiles its fixed-point requant constants
    from the static calibration, so it requires the scale pools to hold
    ``attn.KV_SCALE`` — exactly what the serving layout writes; concrete
    non-uniform scale arrays are rejected with a ValueError (traced arrays
    — the serving cache pools — are trusted by construction). Per-block
    calibration (non-uniform scale arrays) is honored by the ``pallas`` /
    ``interpret`` kernel and the dequant oracle.
    """
    backend = resolve_backend(backend)
    if q.shape[1] % k_pool.shape[1]:
        raise ValueError(
            f"query heads {q.shape[1]} not a multiple of kv heads "
            f"{k_pool.shape[1]}")
    if k_pool.dtype != jnp.int8 or v_pool.dtype != jnp.int8:
        raise ValueError(
            f"paged_attention_int8 needs int8 pools, got "
            f"{k_pool.dtype}/{v_pool.dtype} — float pools go through "
            f"paged_attention")
    from repro.models.attention import KV_SCALE, Q_SCALE

    if backend in ("pallas", "interpret"):
        n = k_pool.shape[0]
        if k_scale is None:
            k_scale = jnp.full((n,), KV_SCALE, jnp.float32)
        if v_scale is None:
            v_scale = jnp.full((n,), KV_SCALE, jnp.float32)
        return paged_attention_int8_pallas(
            q, k_pool, v_pool, block_table, lens, k_scale, v_scale,
            q_scale=Q_SCALE, window=window, start=start,
            interpret=backend == "interpret")
    if backend == "xla":
        # the ITA oracle's fixed-point requant constants are compiled from
        # the static KV_SCALE; a non-uniform scale pool would be silently
        # mis-scaled here. Serving passes the (uniformly KV_SCALE) cache
        # scale pools as tracers — those are trusted by construction — but
        # concrete arrays from direct callers are checked.
        for name, scale in (("k_scale", k_scale), ("v_scale", v_scale)):
            if scale is None or isinstance(scale, jax.core.Tracer):
                continue
            vals = np.asarray(scale)
            if not np.all(vals == np.float32(KV_SCALE)):
                raise ValueError(
                    f"paged_attention_int8 backend='xla' (ITA integer "
                    f"pipeline) supports only the static KV_SCALE "
                    f"calibration, but {name} has per-block values — use "
                    f"the 'pallas'/'interpret' kernel (or the dequant "
                    f"oracle) for per-block calibration")
        return paged_attention_int8_ref(
            q, k_pool, v_pool, block_table, lens, window=window, start=start)
    raise ValueError(f"unknown backend {backend!r}")


@hot_path
def paged_attention_verify(
    q: jax.Array,            # [B, Hq, Q, D] float (post-RoPE), Q = k + 1
    k_pool: jax.Array,       # [N, Hkv, block_len, D]
    v_pool: jax.Array,       # [N, Hkv, block_len, D]
    block_table: jax.Array,  # [B, M] int32 pool indices
    lens: jax.Array,         # [B] int32: committed_len + 1
    *,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Small-q verify attention for speculative decoding.

    Query row ``j`` of each batch row scores draft position
    ``committed + j`` against ``lens + j`` keys (its committed history
    plus the ``j`` draft K/V entries written before it this dispatch).
    Row 0 is exactly a decode step — with ``Q == 1`` every backend here
    matches ``paged_attention`` bit-for-bit, which is what keeps the
    speculative engine token-identical to the plain one.
    """
    backend = resolve_backend(backend)
    if q.shape[1] % k_pool.shape[1]:
        raise ValueError(
            f"query heads {q.shape[1]} not a multiple of kv heads "
            f"{k_pool.shape[1]}")
    if backend in ("pallas", "interpret"):
        return paged_attention_verify_pallas(
            q, k_pool, v_pool, block_table, lens, window=window, start=start,
            interpret=backend == "interpret")
    if backend == "xla":
        return paged_attention_verify_ref(
            q, k_pool, v_pool, block_table, lens, window=window, start=start)
    raise ValueError(f"unknown backend {backend!r}")


@hot_path
def paged_attention_verify_int8(
    q: jax.Array,            # [B, Hq, Q, D] float (post-RoPE)
    k_pool: jax.Array,       # [N, Hkv, block_len, D] int8
    v_pool: jax.Array,       # [N, Hkv, block_len, D] int8
    block_table: jax.Array,  # [B, M] int32 pool indices
    lens: jax.Array,         # [B] int32: committed_len + 1
    *,
    k_scale: Optional[jax.Array] = None,  # [N] f32 per-block (None→KV_SCALE)
    v_scale: Optional[jax.Array] = None,
    window: Optional[int] = None,
    start: Optional[jax.Array] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Int8 small-q verify attention (same numerics split as decode:
    ``xla`` is the exact multi-q ITA oracle, ``pallas``/``interpret`` the
    fused dequant kernel contracted to
    ``ref.paged_attention_verify_int8_dequant_ref``)."""
    backend = resolve_backend(backend)
    if q.shape[1] % k_pool.shape[1]:
        raise ValueError(
            f"query heads {q.shape[1]} not a multiple of kv heads "
            f"{k_pool.shape[1]}")
    if k_pool.dtype != jnp.int8 or v_pool.dtype != jnp.int8:
        raise ValueError(
            f"paged_attention_verify_int8 needs int8 pools, got "
            f"{k_pool.dtype}/{v_pool.dtype} — float pools go through "
            f"paged_attention_verify")
    from repro.models.attention import KV_SCALE, Q_SCALE

    if backend in ("pallas", "interpret"):
        n = k_pool.shape[0]
        if k_scale is None:
            k_scale = jnp.full((n,), KV_SCALE, jnp.float32)
        if v_scale is None:
            v_scale = jnp.full((n,), KV_SCALE, jnp.float32)
        return paged_attention_verify_int8_pallas(
            q, k_pool, v_pool, block_table, lens, k_scale, v_scale,
            q_scale=Q_SCALE, window=window, start=start,
            interpret=backend == "interpret")
    if backend == "xla":
        for name, scale in (("k_scale", k_scale), ("v_scale", v_scale)):
            if scale is None or isinstance(scale, jax.core.Tracer):
                continue
            vals = np.asarray(scale)
            if not np.all(vals == np.float32(KV_SCALE)):
                raise ValueError(
                    f"paged_attention_verify_int8 backend='xla' (ITA "
                    f"integer pipeline) supports only the static KV_SCALE "
                    f"calibration, but {name} has per-block values — use "
                    f"the 'pallas'/'interpret' kernel (or the dequant "
                    f"oracle) for per-block calibration")
        return paged_attention_verify_int8_ref(
            q, k_pool, v_pool, block_table, lens, window=window, start=start)
    raise ValueError(f"unknown backend {backend!r}")
