"""Execution backends behind the ``CacheBackend`` protocol.

A backend owns *where KV state lives and how a token gets computed*; it
knows nothing about queues, QoS classes, lifecycle states, stop
sequences, or streaming — that is ``repro.serve.api.LLMEngine``'s job,
with policy delegated to ``repro.serve.scheduler``.

Three implementations (selected by ``EngineConfig.backend``):

  * ``slot``  — :class:`SlotBackend`: the sequential per-slot reference.
    One batch-1 jitted decode per slot with a host argmax sync per token;
    greedy-only. The numerical baseline the vectorized backends are
    measured against.
  * ``arena`` — :class:`ArenaBackend`: the vectorized dense arena. All
    slots share one fixed-shape ``[slots, max_len, ...]`` cache with
    per-slot position vectors; one jitted batched decode dispatch and one
    device→host token fetch per iteration; pow2-bucketed prefill.
  * ``paged`` — :class:`PagedBackend`: continuous batching over a shared
    pool of fixed-size KV blocks (``models.cache.PagedLayout``) with
    host-owned block tables, ring blocks for sliding-window layers,
    paged prefill straight into pool blocks, and native int8 block
    storage (+ per-block scales) for quantized archs.

The CacheBackend protocol (duck-typed; see ``_BackendBase``):

  ``vectorized``            — True: decode/prefill return on-device token
                              arrays fetched once per iteration by the
                              engine; False: they return host ints and the
                              backend counts its own transfers.
  ``max_admit``             — per-iteration admission cap (None → the
                              engine's ``admit_batch``).
  ``validate_request(req)``    — submit-time checks (capacity, support).
  ``begin_iteration(active, slots)`` — host bookkeeping before the decode
                              dispatch (paged: block growth, ring rotate).
  ``decode(active, slots, samp, any_sampling)`` — one decode pass over
                              the slots.
  ``prefill(req, slot, samp, any_sampling)`` — admit ``req``'s
                              continuation into ``slot``; returns its
                              first sampled token.
  ``can_admit(req)``        — capacity check for admitting ``req`` now.
  ``release(slot, req)``    — recycle a slot's resources (paged: return
                              full-arena *and* ring-arena blocks to the
                              allocators — also the abort path).
  ``evict_for(req, candidates, slots)`` — forced-admission eviction:
                              release as many candidate slots (in order)
                              as ``req`` needs; returns the evicted slots.

INT8 serving (``serve_quant``): K/V are requantized *at write time* on
every path — prefill fill, dense-arena decode write, paged block writes —
so all backends hold the same integers. The dense arenas keep
``compute_dtype`` storage (the requantized integers are exactly
representable), while the paged pool stores the same integers natively as
int8 blocks plus per-block scales — half the resident bytes per token —
and decodes them through ``kernels.paged_attention.paged_attention_int8``.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.annotations import hot_path
from repro.models import registry
from repro.models.cache import (
    BlockAllocator, PagedLayout, blocks_for, bucket_for, cache_insert,
    chain_key, chain_seed, prefix_chain_keys, ring_blocks_for,
    ring_table_row,
)
from repro.serve.config import EngineConfig
from repro.serve.request import Request


def sample_tokens_per_slot(logits: jax.Array, temps: jax.Array,
                           topks: jax.Array, rids: jax.Array,
                           steps: jax.Array, base_key, *,
                           any_sampling: bool = True) -> jax.Array:
    """[B, V] logits + per-slot sampling vectors → [B] int32 tokens.

    Per-request decode-time sampling, fused into the jitted step:
    ``temps[i] <= 0`` decodes row ``i`` greedily; ``topks[i] > 0``
    restricts sampling to the top-k logits (ties at the threshold are
    kept — deterministic and batch-size independent). The PRNG is
    stateless: row ``i`` draws with ``fold_in(fold_in(base_key, rids[i]),
    steps[i])`` where ``steps[i]`` is the request's output-token index, so
    a request's sequence is a pure function of (seed, rid, index) —
    identical whether it decodes alone, in any mixed batch, on either
    vectorized backend, or across a preemption's re-prefill continuation.

    ``any_sampling`` is a *static* host-known flag: the engine sets it
    False when every dispatched row is greedy (the default workload), so
    the all-greedy hot path stays a plain argmax — no full-vocab sort, no
    discarded categorical draw.
    """
    f = logits.astype(jnp.float32)
    greedy_tok = jnp.argmax(f, axis=-1).astype(jnp.int32)
    if not any_sampling:
        return greedy_tok
    vocab = f.shape[-1]
    k_eff = jnp.where(topks > 0, jnp.clip(topks, 1, vocab), vocab)
    sorted_desc = jnp.flip(jnp.sort(f, axis=-1), axis=-1)
    thresh = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], axis=-1)
    masked = jnp.where(f >= thresh, f, -jnp.inf)
    scaled = masked / jnp.maximum(temps, 1e-6)[:, None]
    keys = jax.vmap(
        lambda r, s: jax.random.fold_in(jax.random.fold_in(base_key, r), s)
    )(jnp.asarray(rids, jnp.int32), jnp.asarray(steps, jnp.int32))
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy_tok)


# device memory a fitted pool leaves free for what a step needs beside
# it: the f32 copy of the unembedding table and the logits in decode, the
# activations of one prefill bucket (PagedBackend._fit_num_blocks)
POOL_RESERVE_BYTES = 1 << 30


def _build_qparams(arch: registry.Arch, params):
    # under jit: eagerly, each layer stack's f32 upcast and its
    # intermediates would sit on the device beside the params
    if arch.cfg.serve_quant and arch.quantize_params is not None and (
            arch.cfg.family in ("dense", "vlm-dense")):
        return jax.jit(arch.quantize_params)(params)
    return None


def continuation_tokens(req: Request) -> np.ndarray:
    """Prompt plus already-generated tokens — the re-prefill input after a
    preemption (greedy decode resumes token-identically)."""
    return np.concatenate([np.asarray(req.prompt, np.int32),
                           np.asarray(req.output, np.int32)])


class _BackendBase:
    """State + counters shared by all backends."""

    vectorized = True
    max_admit: Optional[int] = None   # None → EngineConfig.admit_batch
    chunking = False                  # chunked-prefill admission path
    spec_supported = False            # speculative-verify decode path

    def __init__(self, arch: registry.Arch, params, ec: EngineConfig):
        self.arch = arch
        self.params = params
        self.ec = ec
        self.qparams = _build_qparams(arch, params)
        # observability: the one-dispatch / one-transfer / bucketed-trace
        # contract is asserted from these in benchmarks and tests
        self.decode_dispatches = 0
        self.transfers = 0
        self.decode_traces = 0
        self.prefill_traces = 0

    # -- protocol defaults -------------------------------------------------

    def validate_request(self, req: Request) -> None:
        """Submit-time backend checks (engine already checked max_len)."""

    def begin_iteration(self, active: List[int],
                        slots: Sequence[Optional[Request]]) -> None:
        """Host bookkeeping before this iteration's decode dispatch."""

    def can_admit(self, req: Request) -> bool:
        return True

    def choose_slot(self, req: Request,
                    avail: Sequence[int]) -> Optional[int]:
        """Pick the slot ``req`` is admitted into, from the engine's
        free-slot list (in preference order). ``None`` means no listed
        slot can take the request right now. Block-sharded paged serving
        overrides this — slots are pinned to the device owning their
        blocks, so slot choice is a placement decision there."""
        return avail[0] if avail else None

    def release(self, slot: int, req: Request) -> None:
        """Recycle ``slot``'s resources (finish, preemption, abort)."""

    def forget(self, req: Request) -> None:
        """Drop any per-rid bookkeeping for a request that leaves the
        engine *without* ever holding a slot (queued abort, or a
        preempted request finishing on its pre-eviction token). Backends
        that memoize per-rid state must invalidate it here, or a reused
        rid can observe the predecessor's entries."""

    def evict_for(self, req: Request, candidates: List[int],
                  slots: Sequence[Optional[Request]]) -> List[int]:
        """Release candidate slots (in preference order) until ``req``
        fits; returns the slots evicted. Dense backends need exactly one
        victim — capacity is per-slot."""
        victim = candidates[0]
        self.release(victim, slots[victim])
        return [victim]


class ArenaBackend(_BackendBase):
    """Vectorized dense-arena backend (the default).

    One fixed-shape ``[slots, max_len, ...]`` batched cache with a
    per-slot position vector; one jitted batched decode over the whole
    batch per iteration; on-device sampling; pow2 length-bucketed prefill
    spliced into the arena with ``models.cache.cache_insert``. Free slots
    keep computing — the decode shape never changes; finished or empty
    slots produce garbage rows that are ignored host-side and overwritten
    by the next admission.

    Under ``serve_quant`` every write path (prefill fill + decode write)
    requantizes first, so the arena holds exactly the integers the int8
    paged pool stores natively — this backend is the numerical reference
    for both.
    """

    name = "arena"

    def __init__(self, arch: registry.Arch, params, ec: EngineConfig):
        super().__init__(arch, params, ec)
        self.cache = arch.init_cache(ec.slots, ec.max_len, quantized=False)
        self.last_tok = jnp.zeros((ec.slots,), jnp.int32)
        base_key = jax.random.key(ec.seed)
        self._bucketing = ec.prefill_buckets and arch.supports_padded_prefill

        def _dec(p, qp, cache, last_tok, samp, any_sampling):
            # repro: allow(retrace-hazard) -- deliberate trace counter
            self.decode_traces += 1  # runs at trace time only
            if qp is None:
                logits, cache = arch.decode_step(p, cache, last_tok)
            else:
                logits, cache = arch.decode_step(p, cache, last_tok,
                                                 qparams=qp)
            # fused per-slot sampling (stateless PRNG: see above)
            tok = sample_tokens_per_slot(logits, *samp, base_key,
                                         any_sampling=any_sampling)
            return tok, cache

        def _insert_and_sample(logits, c1, slot, cache, last_tok, samp,
                               any_sampling):
            cache = cache_insert(cache, c1, slot)
            tok = sample_tokens_per_slot(logits, *samp, base_key,
                                         any_sampling=any_sampling)  # [1]
            last_tok = jax.lax.dynamic_update_slice(last_tok, tok, (slot,))
            return tok[0], cache, last_tok

        def _pre_bucketed(p, tokens, true_len, slot, cache, last_tok, samp,
                          embeds, any_sampling):
            # repro: allow(retrace-hazard) -- deliberate trace counter
            self.prefill_traces += 1  # one trace per bucket, not length
            logits, c1 = arch.prefill(p, tokens, ec.max_len,
                                      true_len=true_len, embeds=embeds)
            return _insert_and_sample(logits, c1, slot, cache, last_tok,
                                      samp, any_sampling)

        def _pre_exact(p, tokens, slot, cache, last_tok, samp, embeds,
                       any_sampling):
            # repro: allow(retrace-hazard) -- deliberate trace counter
            self.prefill_traces += 1
            logits, c1 = arch.prefill(p, tokens, ec.max_len, embeds=embeds)
            return _insert_and_sample(logits, c1, slot, cache, last_tok,
                                      samp, any_sampling)

        # Donate the cache arena: in-place slot updates instead of a whole-
        # arena copy per token. last_tok is NOT donated — it is fetched
        # (device_get) after the next dispatch has already consumed it.
        # any_sampling is static: the all-greedy workload compiles to a
        # plain argmax (one extra trace only when sampling rows appear).
        self._decode_fn = jax.jit(_dec, donate_argnums=(2,),
                                  static_argnums=(5,))
        self._prefill_bucketed = jax.jit(_pre_bucketed, donate_argnums=(4,),
                                         static_argnums=(8,))
        self._prefill_exact = jax.jit(_pre_exact, donate_argnums=(3,),
                                      static_argnums=(7,))

    def _bucket_ok(self, bucket: int) -> bool:
        # ring (sliding-window) caches drop leading positions once the
        # prefill length exceeds the window — only bucket under it
        cfg = self.arch.cfg
        return "L" not in cfg.pattern or bucket <= cfg.local_window

    @hot_path
    def decode(self, active, slots, samp, any_sampling):
        tok, self.cache = self._decode_fn(
            self.params, self.qparams, self.cache, self.last_tok,
            samp, any_sampling)
        self.last_tok = tok
        self.decode_dispatches += 1
        return tok

    @hot_path
    def prefill(self, req: Request, slot: int, samp, any_sampling):
        """One prefill dispatch for ``req`` into ``slot``; returns the
        on-device sampled first token (fetched later, with the batch)."""
        toks = continuation_tokens(req)
        n = toks.size
        embeds = None if req.embeds is None else jnp.asarray(req.embeds)[None]
        bucket = bucket_for(n, self.ec.min_bucket, self.ec.max_len)
        if self._bucketing and self._bucket_ok(bucket):
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = toks
            tok, self.cache, self.last_tok = self._prefill_bucketed(
                self.params, jnp.asarray(padded),
                jnp.asarray(n, jnp.int32), jnp.asarray(slot, jnp.int32),
                self.cache, self.last_tok, samp, embeds, any_sampling)
        else:
            tok, self.cache, self.last_tok = self._prefill_exact(
                self.params, jnp.asarray(toks[None, :]),
                jnp.asarray(slot, jnp.int32),
                self.cache, self.last_tok, samp, embeds, any_sampling)
        return tok


class SlotBackend(_BackendBase):
    """Sequential per-slot reference backend (pre-batching baseline).

    Decodes each slot with a batch-1 jitted call and syncs to host for the
    argmax of every token of every slot — kept as the numerical reference
    for the vectorized backends and as the benchmark baseline. Prefill is
    jitted per prompt length (the retrace cost the bucketed path removes).
    Greedy-only; admits at most one request per iteration.
    """

    name = "slot"
    vectorized = False
    max_admit = 1

    def __init__(self, arch: registry.Arch, params, ec: EngineConfig):
        super().__init__(arch, params, ec)
        if not ec.greedy:
            raise NotImplementedError(
                "reference engine is greedy-only; use the arena backend")
        self.caches = [None] * ec.slots

        def _dec(p, c, t):
            # repro: allow(retrace-hazard) -- deliberate trace counter
            self.decode_traces += 1  # runs at trace time only
            if self.qparams is None:
                return arch.decode_step(p, c, t)
            return arch.decode_step(p, c, t, qparams=self.qparams)

        def _pre(p, t, embeds):
            # repro: allow(retrace-hazard) -- deliberate trace counter
            self.prefill_traces += 1  # retraces per new prompt length
            return arch.prefill(p, t, ec.max_len, embeds=embeds)

        self._decode = jax.jit(_dec)
        self._prefill = jax.jit(_pre)

    def validate_request(self, req: Request) -> None:
        # greedy-only reference: refuse rather than silently decode a
        # sampling request with argmax
        if self.ec.effective_temperature(req.temperature) > 0 \
                or req.top_k > 0:
            raise NotImplementedError(
                f"reference engine is greedy-only and would ignore request "
                f"{req.rid}'s temperature/top_k; use the arena backend")

    def decode(self, active, slots, samp, any_sampling):
        """Batch-1 decode per active slot, host argmax sync per token —
        returns ``{slot: host token}`` (the engine skips the device fetch
        for non-vectorized backends)."""
        out = {}
        for slot in active:
            req = slots[slot]
            last = jnp.asarray([req.output[-1]], jnp.int32)
            logits, self.caches[slot] = self._decode(
                self.params, self.caches[slot], last)
            self.decode_dispatches += 1
            out[slot] = int(jnp.argmax(logits[0]))  # host sync (counted)
            self.transfers += 1
        return out

    def prefill(self, req: Request, slot: int, samp, any_sampling):
        toks = jnp.asarray(continuation_tokens(req)[None, :], jnp.int32)
        embeds = None if req.embeds is None else jnp.asarray(req.embeds)[None]
        logits, cache = self._prefill(self.params, toks, embeds)
        tok = int(jnp.argmax(logits[0]))  # host sync (counted)
        self.transfers += 1
        self.caches[slot] = cache
        return tok

    def release(self, slot: int, req: Request) -> None:
        self.caches[slot] = None


def validate_paged_config(arch: registry.Arch, attn_backend: str):
    """Config validation for the paged backend. After ring blocks + paged
    prefill, every attention-cache family serves on the paged path for any
    ``local_window``; what remains unsupported is recurrent state (no
    growing KV to page). Quantized (``serve_quant``) archs additionally
    need int8 block-pool support — both in the family (write-time
    requantization + int8 decode) and in the configured attention backend
    (the fused int8 kernel / ITA oracle). All of it fails *here*, at
    construction, with the arch named in the error — never mid-serve
    inside a jitted step."""
    from repro.kernels.paged_attention import ops as paged_ops

    cfg = arch.cfg
    if not arch.supports_paged:
        bad = "".join(sorted(set(cfg.pattern) - set("GLB")))
        why = (f"layer kinds {bad!r} keep recurrent state, which has no "
               f"growing KV cache to page" if bad else
               "the family does not implement paged_decode_step")
        raise ValueError(
            f"paged serving: family {cfg.family!r} (layer pattern "
            f"{cfg.pattern!r}) has no paged decode path — {why}; use "
            f"the arena backend for this arch")
    if not arch.supports_paged_prefill:
        raise ValueError(
            f"paged serving: family {cfg.family!r} has a paged decode path "
            f"but no paged prefill — implement `paged_prefill` next to its "
            f"`paged_decode_step`")
    if cfg.serve_quant:
        if not arch.supports_paged_int8:
            raise ValueError(
                f"paged serving: arch {cfg.name!r} (family {cfg.family!r}) "
                f"is quantized (serve_quant) but the family does not "
                f"support int8 block pools — set serve_quant=False or add "
                f"write-time requantization + PAGED_INT8_KV to the family")
        if attn_backend not in paged_ops.INT8_BACKENDS:
            raise ValueError(
                f"paged serving: arch {cfg.name!r} is quantized "
                f"(serve_quant) but attention backend {attn_backend!r} "
                f"does not implement the int8 paged-attention kernel "
                f"(supported: {', '.join(paged_ops.INT8_BACKENDS)}) — "
                f"pick one of those or serve the float path")
    elif attn_backend not in paged_ops.BACKENDS:
        raise ValueError(
            f"paged serving: unknown attention backend {attn_backend!r} "
            f"(supported: {', '.join(paged_ops.BACKENDS)})")


class PagedBackend(_BackendBase):
    """Continuous batching over a paged block-pool KV cache.

    The dense arena reserves ``max_len`` KV rows per slot, so short
    requests strand arena capacity that long ones need — the fragmentation
    that CHIMERA's *banked, interleaved* shared-L2 island avoids in
    hardware. Here KV state lives in a shared pool of fixed-size blocks
    (``models.cache.PagedLayout``); each slot holds a block table mapping
    position ``p`` to pool block ``table[slot, p // block_len]``. A
    host-side free-list allocator (``models.cache.BlockAllocator``) admits
    against *worst-case* block reservations, grows slots lazily at block
    boundaries, and recycles blocks on completion, preemption and abort —
    so at a fixed KV-memory budget the paged backend admits every mix of
    lengths the budget can actually hold, not ``budget / max_len`` slots.

    **Ring blocks** (sliding-window "L" layers with ``local_window <
    max_len``): L-layer pools are a separate, much smaller arena — each
    slot owns a fixed ring of ``ceil(window/block_len) + 1`` blocks and
    reuses them circularly. The host rotates the per-slot ring table as
    the window slides (entry 0 = oldest live block) and passes its
    block-aligned absolute start position into the step, so the kernel
    masks by absolute position and wrapped blocks attend correctly.

    **Paged prefill**: admission runs ``arch.paged_prefill``, which writes
    K/V straight into pool blocks (full blocks in bulk, the tail at block
    granularity) — no dense bucket cache, no splice dispatch.

    **Int8 blocks** (``serve_quant`` archs): pools store K/V natively as
    int8 plus per-block scales — roughly half the resident bytes per token
    of a bf16 pool — and decode runs ``paged_attention_int8`` over the
    blocks (ITA gather oracle on ``xla``, token-identical to the dense
    int8 reference; fused dequantizing kernel on ``pallas``/``interpret``).

    **Mesh sharding** (``mesh=`` a ``jax.sharding.Mesh`` with a ``model``
    axis): the software twin of CHIMERA's shared-L2 island interleaving
    banks across clusters — pool capacity and read bandwidth scale with
    device count at a fixed per-device budget. The decode/prefill steps
    run under ``shard_map``; strategy comes from
    ``parallel.sharding.pick_paged_serve_rules``. In **heads** mode
    (KV head count divides the mesh) each device holds a KV-head slice of
    every pool; layers slice Q/K/V locally and all-gather the attention
    output — one collective per layer, bit-identical to single-device. In
    **blocks** mode (the fallback) each device owns ``num_blocks / ndev``
    pool blocks plus its own trash block; slots pin to device
    ``slot % ndev`` with per-device allocators, tables and prefix caches,
    and the owner's rows win via an exact masked psum. Sampling always
    runs on the replicated logits outside the shard-mapped region, so the
    one-dispatch / one-transfer contract is unchanged.

    The dataflow contract is preserved: one jitted paged decode dispatch
    over all rows per iteration, up to ``admit_batch`` admission
    dispatches, one device→host token fetch. Tables are host-owned and
    passed into the jitted step each call (fixed shapes — no retrace);
    empty rows decode against the dedicated trash block and are ignored
    host-side.
    """

    name = "paged"

    def __init__(self, arch: registry.Arch, params, ec: EngineConfig,
                 mesh=None):
        super().__init__(arch, params, ec)
        cfg = arch.cfg
        from repro.kernels import resolve_backend

        self.attn_backend = resolve_backend(ec.attn_backend)
        validate_paged_config(arch, self.attn_backend)
        # -- mesh resolution ------------------------------------------------
        # mesh=None is the single-device path (unchanged). With a mesh the
        # pool shards per ``pick_paged_serve_rules``: "heads" slices the
        # KV-head axis (layers slice Q/K/V, attend locally, all-gather the
        # attention output — bit-identical); "blocks" is the fallback when
        # the head count doesn't divide the mesh — each device owns a
        # slice of num_blocks, slots pin to the device holding their
        # blocks, and the owner's rows are selected by a masked psum.
        self.mesh = mesh
        self.kv_mode: Optional[str] = None
        self.ndev = 1
        self._cache_specs = None
        rules = None
        if mesh is not None:
            from repro.parallel.sharding import pick_paged_serve_rules
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            if "model" not in sizes:
                raise ValueError(
                    f"paged serving mesh needs a 'model' axis, got "
                    f"{mesh.axis_names}")
            extra = [a for a in mesh.axis_names
                     if a != "model" and sizes[a] != 1]
            if extra:
                raise ValueError(
                    f"paged serving shards over 'model' only; mesh axes "
                    f"{extra} have extent > 1")
            self.ndev = sizes["model"]
            rules, self.kv_mode = pick_paged_serve_rules(
                cfg, mesh, kv_shard=ec.kv_shard)
        # ring blocks when sliding-window layers can't hold full history
        self.ring = ("L" in cfg.pattern
                     and cfg.local_window < ec.max_len
                     and cfg.family != "encdec")
        num_blocks = ec.num_blocks
        self.pool_sizing: Dict[str, int] = {}
        if num_blocks is None:  # match the dense arena's token budget
            num_blocks = blocks_for(ec.slots * ec.max_len, ec.block_len) + 1
            if not self.ring:  # ... or what the device's memory holds
                num_blocks = self._fit_num_blocks(num_blocks)
        if self.kv_mode == "blocks":
            # each device owns an equal slice of the pool (local block 0
            # is that device's trash row); round up so the pool splits
            # evenly and every device keeps at least one usable block
            num_blocks = max(num_blocks, 2 * self.ndev)
            num_blocks = -(-num_blocks // self.ndev) * self.ndev
        wb = ring_blocks_for(cfg.local_window, ec.block_len) if self.ring \
            else 0
        self.layout = PagedLayout(
            ec.block_len, num_blocks, ec.max_len,
            window=cfg.local_window if self.ring else None,
            ring_num_blocks=(1 + ec.slots * wb) if self.ring else 0)
        # blocks mode: admission/growth run against per-device allocators
        # over each device's local slice; otherwise one global allocator
        # over the whole pool (sliced by head, not by block)
        self._dev_layout = (
            PagedLayout(ec.block_len, num_blocks // self.ndev, ec.max_len)
            if self.kv_mode == "blocks" else self.layout)
        # content-addressed prefix caching: full-history layouts only —
        # a ring layout skipping its prefix prefill would leave the
        # sliding-window pools unwritten for in-window prefix positions
        self.prefix_caching = bool(ec.prefix_cache) and not self.ring
        if self.kv_mode == "blocks":
            self.alloc = None
            self.allocs: Optional[List[BlockAllocator]] = [
                BlockAllocator(self._dev_layout,
                               prefix_cache=self.prefix_caching)
                for _ in range(self.ndev)]
        else:
            self.alloc = BlockAllocator(self.layout,
                                        prefix_cache=self.prefix_caching)
            self.allocs = None
        # full-history blocks are consumed by non-L layers only; an all-L
        # pattern reserves none of them
        self._has_full = (not self.ring) or any(k != "L" for k in cfg.pattern)
        if self.kv_mode == "blocks":
            # one table plane per device holding *local* block ids; a
            # slot's non-owner planes stay 0 (each device's trash block),
            # so every device runs identical shapes and non-owner writes
            # land in trash. Slot i's owner is device i % ndev.
            self.table = np.zeros(
                (self.ndev, ec.slots, self.layout.max_blocks), np.int32)
        else:
            self.table = np.zeros((ec.slots, self.layout.max_blocks),
                                  np.int32)
        if self.ring:
            # the ring arena always fits every slot's ring (sized above),
            # but runs through an allocator so leaks/double-frees surface
            self.ring_alloc = BlockAllocator(PagedLayout(
                ec.block_len, self.layout.ring_num_blocks, ec.max_len))
            self.ring_table = np.zeros((ec.slots, wb), np.int32)
            self.ring_start = np.zeros((ec.slots,), np.int32)
            self._ring_first = [0] * ec.slots   # abs block idx of entry 0
            self._ring_ids: List = [None] * ec.slots
        self._slot_len = [0] * ec.slots   # host mirror of active rows' len
        self._tables_dev = None           # cached device view of the tables
        # prefix cache: per-slot chain keys of the full blocks written so
        # far (prompt at prefill, decode blocks as they complete), plus
        # skip counters for metrics/bench
        self._slot_keys: List[List[bytes]] = [[] for _ in range(ec.slots)]
        # chain-key memo (rid -> (continuation_len, keys)): can_admit() runs
        # for every queued request every iteration, and the sha256 chain over
        # a long shared prefix is the dominant host cost of admission under
        # load. Bounded LRU; entries are dropped at prefill/release and
        # invalidated by continuation growth (preempted requeues).
        self._key_memo: "OrderedDict[int, Tuple[int, List[bytes]]]" = \
            OrderedDict()
        self.prefill_tokens_skipped = 0
        self.prefill_tokens_total = 0
        # chunked prefill: admissions split into block-aligned chunks
        # co-scheduled with decode. Rings opt out (a ring arena cannot
        # resume mid-history — same reason they opt out of prefix
        # caching); the engine falls back to monolithic prefill there.
        self.chunking = (ec.prefill_chunk_tokens is not None
                         and not self.ring)
        # per-slot mid-chunk admission state (set by prefill_begin,
        # cleared at the final chunk or on release)
        self._chunk: Dict[int, dict] = {}
        self.prefill_chunk_dispatches = 0
        # speculative decoding: the engine replaces the decode dispatch
        # with a small-q verify over host-drafted tokens. Rings opt out
        # (ring rotation assumes one position per iteration) and
        # mesh-sharded pools opt out (no shard_map verify path yet) —
        # silently, like chunked prefill; the engine falls back to plain
        # decode there.
        self.spec_supported = (arch.supports_spec_decode
                               and not self.ring and mesh is None)
        # quantized archs get int8 block pools (+ per-block scales) — the
        # family default; float archs keep compute_dtype pools
        self.quantized = bool(cfg.serve_quant)
        self.last_tok = jnp.zeros((ec.slots,), jnp.int32)
        if mesh is None:
            self.cache = arch.init_paged_cache(ec.slots, self.layout)
        else:
            # build the cache at global logical shapes straight into its
            # mesh layout per the picked rules (never whole on one
            # device: that would need the mesh's pool on one chip);
            # params (and the replicated host-table uploads each
            # iteration) stay replicated. Keeping matching out_specs
            # below holds the cache sharded in steady state with
            # donation intact.
            from jax.sharding import NamedSharding, PartitionSpec as P
            from jax import shard_map
            from repro.models.cache import KVShard
            from repro.parallel.sharding import paged_cache_axes

            def init_cache():
                return arch.init_paged_cache(ec.slots, self.layout)

            shapes = jax.eval_shape(init_cache)
            axes = paged_cache_axes(cfg, shapes, ring=self.ring)
            self._cache_specs = rules.tree_spec(axes, mesh, like=shapes)
            self.cache = jax.jit(init_cache, out_shardings=jax.tree.map(
                lambda s: NamedSharding(mesh, s), self._cache_specs,
                is_leaf=lambda x: isinstance(x, P)))()
            rep = NamedSharding(mesh, P())
            self.params = jax.device_put(self.params, rep)
            if self.qparams is not None:
                self.qparams = jax.device_put(self.qparams, rep)
            self.last_tok = jax.device_put(self.last_tok, rep)
        base_key = jax.random.key(ec.seed)
        self._bucketing = ec.prefill_buckets and arch.supports_padded_prefill
        backend = self.attn_backend
        mode, ndev, cache_specs = self.kv_mode, self.ndev, self._cache_specs
        if mesh is not None:
            # decode owner is static: slot i's blocks live on device
            # i % ndev. Block-id operands ([ndev]- or [ndev, nb]-shaped in
            # blocks mode, owner plane real / others 0) shard over the
            # mesh so each device sees only its local ids.
            owner_dec = (jnp.asarray(
                np.arange(ec.slots, dtype=np.int32) % ndev)
                if mode == "blocks" else None)
            idspec = P("model") if mode == "blocks" else P()
            if mode == "blocks":
                table_spec = ({"full": P("model"), "ring": P(), "start": P()}
                              if self.ring else P("model"))
            else:
                table_spec = P()

        def _model_dec(p, qp, cache, table, last_tok):
            if mesh is None:
                return arch.paged_decode_step(
                    p, cache, last_tok, table, qparams=qp,
                    attn_backend=backend)

            def body(p, qp, cache, table, last_tok):
                shard = KVShard(mode, nshard=ndev, owner=owner_dec)
                if mode == "blocks":
                    if isinstance(table, dict):
                        table = dict(table, full=table["full"][0])
                    else:
                        table = table[0]
                return arch.paged_decode_step(
                    p, cache, last_tok, table, qparams=qp,
                    attn_backend=backend, shard=shard)

            return shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(), cache_specs, table_spec, P()),
                out_specs=(P(), cache_specs), check_vma=False,
            )(p, qp, cache, table, last_tok)

        def _dec(p, qp, cache, table, last_tok, samp, any_sampling):
            # repro: allow(retrace-hazard) -- deliberate trace counter
            self.decode_traces += 1  # runs at trace time only
            logits, cache = _model_dec(p, qp, cache, table, last_tok)
            # sampling runs on the replicated logits *outside* the
            # shard-mapped step — the collectives end at the model output
            tok = sample_tokens_per_slot(logits, *samp, base_key,
                                         any_sampling=any_sampling)
            return tok, cache

        def _model_pre(p, tokens, true_len, slot, block_ids, ring_ids,
                       cache, embeds, prefix_ids, start):
            if mesh is None:
                return arch.paged_prefill(
                    p, tokens, cache, slot, block_ids, ring_ids=ring_ids,
                    true_len=true_len, embeds=embeds,
                    prefix_ids=prefix_ids, start=start)

            def body(p, tokens, true_len, slot, block_ids, ring_ids, cache,
                     embeds, prefix_ids):
                owner = slot % ndev if mode == "blocks" else None
                shard = KVShard(mode, nshard=ndev, owner=owner)
                if mode == "blocks":
                    block_ids = block_ids[0]
                    if prefix_ids is not None:
                        prefix_ids = prefix_ids[0]
                return arch.paged_prefill(
                    p, tokens, cache, slot, block_ids, ring_ids=ring_ids,
                    true_len=true_len, embeds=embeds,
                    prefix_ids=prefix_ids, start=start, shard=shard)

            return shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(), P(), P(), idspec, P(), cache_specs,
                          P(), idspec),
                out_specs=(P(), cache_specs), check_vma=False,
            )(p, tokens, true_len, slot, block_ids, ring_ids, cache,
              embeds, prefix_ids)

        def _pre(p, tokens, true_len, slot, block_ids, ring_ids, cache,
                 last_tok, samp, embeds, prefix_ids, any_sampling, start):
            # repro: allow(retrace-hazard) -- deliberate trace counter
            self.prefill_traces += 1  # one trace per (bucket, blocks)
            logits, cache = _model_pre(p, tokens, true_len, slot, block_ids,
                                       ring_ids, cache, embeds, prefix_ids,
                                       start)
            tok = sample_tokens_per_slot(logits, *samp, base_key,
                                         any_sampling=any_sampling)  # [1]
            last_tok = jax.lax.dynamic_update_slice(last_tok, tok, (slot,))
            return tok[0], cache, last_tok

        def _copy_impl(cache, old, new):
            # copy-on-write: duplicate one pool block (k/v + scales) so a
            # diverging writer stops sharing it; per-slot leaves (encdec
            # cross K/V, positions) are left untouched
            def cp(path, leaf):
                tail = path[-1]
                name = tail.key if isinstance(tail, jax.tree_util.DictKey) \
                    else None
                if name in ("k", "v", "kscale", "vscale"):
                    return leaf.at[:, new].set(leaf[:, old])
                return leaf

            return jax.tree_util.tree_map_with_path(cp, cache)

        def _copy_block(cache, old, new):
            if mesh is None:
                return _copy_impl(cache, old, new)

            def body(cache, old, new):
                # heads mode: every device copies its head slice of the
                # (replicated) block id; blocks mode: the owner copies its
                # local ids, everyone else copies trash onto itself
                if mode == "blocks":
                    old, new = old[0], new[0]
                return _copy_impl(cache, old, new)

            return shard_map(
                body, mesh=mesh,
                in_specs=(cache_specs, idspec, idspec),
                out_specs=cache_specs, check_vma=False)(cache, old, new)

        self._decode_fn = jax.jit(_dec, donate_argnums=(2,),
                                  static_argnums=(6,))
        self._prefill_fn = jax.jit(_pre, donate_argnums=(6,),
                                   static_argnums=(11, 12))
        self._copy_block_fn = jax.jit(_copy_block, donate_argnums=(0,))

        if self.spec_supported:
            def _ver(p, qp, cache, table, packed, samp, any_sampling):
                # repro: allow(retrace-hazard) -- deliberate trace counter
                self.decode_traces += 1  # runs at trace time only
                # packed [B, Q+1]: column 0 is the committed length, the
                # rest the token row — one host→device upload per verify.
                # The position vector is host-owned under speculation:
                # inject this iteration's committed lengths; the verify
                # step never advances them (the host commits)
                lens, tokens = packed[:, 0], packed[:, 1:]
                logits, cache = arch.paged_verify_step(
                    p, dict(cache, len=lens), tokens, table, qparams=qp,
                    attn_backend=backend)
                b, qlen, vocab = logits.shape
                # flat per-position sampling: row i·Q + j carries slot i's
                # coordinates with the *absolute* output index of position
                # j, so a sampled token is a pure function of
                # (seed, rid, index) — identical with speculation on or off
                tok = sample_tokens_per_slot(
                    logits.reshape(b * qlen, vocab), *samp, base_key,
                    any_sampling=any_sampling)
                return tok.reshape(b, qlen), cache

            self._verify_fn = jax.jit(_ver, donate_argnums=(2,),
                                      static_argnums=(6,))

    # -- mesh helpers ------------------------------------------------------

    def _dev(self, slot: int) -> int:
        """Owning device of a slot's blocks (blocks mode pins slot i to
        device i % ndev; degenerate 0 otherwise)."""
        return slot % self.ndev

    def _alloc_for(self, slot: int) -> BlockAllocator:
        if self.kv_mode == "blocks":
            return self.allocs[self._dev(slot)]
        return self.alloc

    def _all_allocs(self) -> List[BlockAllocator]:
        return self.allocs if self.kv_mode == "blocks" else [self.alloc]

    def _set_table(self, slot: int, idx: int, block: int) -> None:
        if self.kv_mode == "blocks":
            self.table[self._dev(slot), slot, idx] = block
        else:
            self.table[slot, idx] = block
        self._touch_tables()

    def _block_arg(self, slot: int, block: int):
        """Block-id operand for the jitted COW copy: an [ndev] vector in
        blocks mode (owner entry real, others 0 → a trash-onto-itself
        no-op); a replicated scalar otherwise."""
        if self.kv_mode == "blocks":
            vec = np.zeros((self.ndev,), np.int32)
            vec[self._dev(slot)] = block
            return jnp.asarray(vec)
        return jnp.asarray(block, jnp.int32)

    # -- capacity bookkeeping ----------------------------------------------

    def _pre_len(self, req: Request) -> int:
        """Prefill cache length for ``req``'s continuation (block multiple;
        pow2 bucket when bucketing). The bucket is capped at the request's
        worst-case decode extent so the block reservation is *invariant
        across preemptions* — a pow2 bucket of a grown continuation must
        never demand more blocks than ``submit`` admitted against, or a
        preempted request could become unreadmittable."""
        blk = self.ec.block_len
        n = len(req.prompt) + len(req.output)
        if self._bucketing:
            bucket = bucket_for(n, max(self.ec.min_bucket, blk),
                                self.ec.max_len)
        else:
            bucket = n
        cap = blocks_for(len(req.prompt) + req.max_new_tokens - 1, blk) * blk
        # round the (possibly max_len-clamped, non-pow2) bucket up to a
        # block multiple; the roundup never exceeds cap because cap is one
        return max(blocks_for(n, blk) * blk,
                   blocks_for(min(bucket, cap), blk) * blk)

    def _max_blocks_needed(self, req: Request) -> int:
        """Worst-case full-history block reservation: the prefill extent
        now, or the final decode position, whichever is larger. An all-L
        pattern consumes no full-history blocks (its ring reservation is a
        fixed ``ring_blocks`` per slot, accounted separately)."""
        if not self._has_full:
            return 0
        final_pos = len(req.prompt) + req.max_new_tokens - 1
        return blocks_for(max(self._pre_len(req), final_pos),
                          self.ec.block_len)

    # -- content-addressed prefix keys -------------------------------------

    @staticmethod
    def _chain_salt(req: Request) -> bytes:
        """Per-request hash-chain salt: requests whose K/V depends on more
        than the token prefix (encdec cross-attends its encoder states)
        must never share blocks across different conditioning inputs."""
        if req.embeds is None:
            return b""
        arr = np.ascontiguousarray(np.asarray(req.embeds, np.float32))
        return hashlib.sha256(arr.tobytes()).digest()

    _KEY_MEMO_CAP = 256

    def _chain_keys(self, req: Request) -> List[bytes]:
        """Chained content keys for every full block of ``req``'s
        continuation (uncapped — slice with ``_hit_limit`` for lookup).
        Memoized per rid on continuation length: a queued request is
        re-keyed by ``can_admit`` every iteration, and the hash chain over
        a long shared prefix would otherwise be recomputed each time."""
        n = len(req.prompt) + len(req.output)
        hit = self._key_memo.get(req.rid)
        if hit is not None and hit[0] == n:
            self._key_memo.move_to_end(req.rid)
            return hit[1]
        keys = prefix_chain_keys(continuation_tokens(req), self.ec.block_len,
                                 salt=self._chain_salt(req))
        self._key_memo[req.rid] = (n, keys)
        self._key_memo.move_to_end(req.rid)
        while len(self._key_memo) > self._KEY_MEMO_CAP:
            self._key_memo.popitem(last=False)
        return keys

    def _hit_limit(self, req: Request) -> int:
        """Max cache-hit blocks: the suffix must keep ≥ 1 real token (the
        last-position logits are computed, never looked up)."""
        n = len(req.prompt) + len(req.output)
        return max(0, (n - 1) // self.ec.block_len)

    def validate_request(self, req: Request) -> None:
        need = self._max_blocks_needed(req)
        usable = self._dev_layout.usable_blocks
        if need > usable:
            where = " per device" if self.kv_mode == "blocks" else ""
            raise ValueError(
                f"request {req.rid} needs {need} blocks; pool has "
                f"{usable}{where}")

    def _admit_keys(self, req: Request) -> Sequence[bytes]:
        if not self.prefix_caching:
            return ()
        return self._chain_keys(req)[:self._hit_limit(req)]

    def can_admit(self, req: Request) -> bool:
        need = self._max_blocks_needed(req)
        keys = self._admit_keys(req)
        if not any(a.can_admit(need, keys) for a in self._all_allocs()):
            return False
        if self.ring and not self.ring_alloc.can_admit(
                self.layout.ring_blocks):
            return False
        return True

    def choose_slot(self, req: Request,
                    avail: Sequence[int]) -> Optional[int]:
        """Blocks mode: admit into a slot whose device both has capacity
        and holds the most cached prefix blocks for this request (ties →
        the engine's preference order). Otherwise: first listed slot."""
        if self.kv_mode != "blocks":
            return avail[0] if avail else None
        need = self._max_blocks_needed(req)
        keys = self._admit_keys(req)
        best, best_hits = None, -1
        for slot in avail:
            a = self.allocs[self._dev(slot)]
            if not a.can_admit(need, keys):
                continue
            hits = len(a.lookup(keys)) if keys else 0
            if hits > best_hits:
                best, best_hits = slot, hits
        return best

    def release(self, slot: int, req: Request) -> None:
        """Recycle a slot's blocks (full + ring) and point its table rows
        at trash. Also the ``abort()`` path. With prefix caching the
        release *decrefs*: shared blocks survive under their other
        references, and published sole-owned blocks move to the cached LRU
        (reusable K/V) instead of the free list."""
        self._alloc_for(slot).release(req.rid)
        if self.kv_mode == "blocks":
            self.table[:, slot, :] = 0
        else:
            self.table[slot, :] = 0
        if self.ring:
            self.ring_alloc.release(req.rid)
            self.ring_table[slot, :] = 0
            self.ring_start[slot] = 0
            self._ring_first[slot] = 0
            self._ring_ids[slot] = None
        self._touch_tables()
        self._slot_len[slot] = 0
        self._slot_keys[slot] = []
        self._key_memo.pop(req.rid, None)
        # aborted/preempted mid-chunk: the chunk cursor dies with the
        # blocks (a re-admission re-prefills from scratch — or from
        # whatever its own published blocks left in the cache)
        self._chunk.pop(slot, None)
        req.prefill_pos = 0

    def forget(self, req: Request) -> None:
        """Invalidate the per-rid chain-key memo for a request that never
        reached ``release`` (queued abort / finish before admission). The
        memo's validity check is continuation *length* only, so a reused
        rid with a different same-length prompt would otherwise inherit
        the predecessor's chain keys and claim false cache hits."""
        self._key_memo.pop(req.rid, None)

    def evict_for(self, req, candidates, slots):
        need = self._max_blocks_needed(req)
        if self.kv_mode == "blocks":
            # victims must share ONE device: freed blocks only help a
            # request admitted into a slot of that same device (the engine
            # re-admits into evict[0]'s slot). Devices are tried in the
            # scheduler's preference order (position of their first
            # candidate); an infeasible device is skipped whole.
            keys = self._admit_keys(req)
            by_dev: Dict[int, List[int]] = {}
            for i in candidates:
                by_dev.setdefault(self._dev(i), []).append(i)
            for d in sorted(by_dev,
                            key=lambda d: candidates.index(by_dev[d][0])):
                a, cands = self.allocs[d], by_dev[d]
                if need > a.available_blocks + sum(
                        a.reservation(slots[i].rid) for i in cands):
                    continue
                single = next(
                    (i for i in cands if a.can_admit_after_release(
                        need, slots[i].rid)), None)
                order = [single] if single is not None else cands
                evicted: List[int] = []
                for victim in order:
                    if evicted and a.can_admit(need, keys) and (
                            not self.ring or self.ring_alloc.can_admit(
                                self.layout.ring_blocks)):
                        break
                    self.release(victim, slots[victim])
                    evicted.append(victim)
                return evicted
            return []
        # Feasibility first: when an admission *this iteration* already
        # reserved blocks (possible under the QoS scheduler, whose forced
        # path fires even alongside admissions), the candidate slots may
        # not hold enough between them — the just-admitted slot is never
        # a victim. Evicting anybody would then be pure waste: bail out
        # and let the request retry next iteration, when the blocker is
        # a normal (evictable) running slot.
        if need > self.alloc.available_blocks + sum(
                self.alloc.reservation(slots[i].rid) for i in candidates):
            return []
        # evict victims (in the scheduler's preference order) until the
        # request's reservation fits; multiple small slots may need to go,
        # since the bounded-priority guarantee must not hinge on any
        # single victim being block-rich enough. Evicting every slot
        # always suffices: validate_request guarantees need ≤
        # usable_blocks, and queued requests hold no blocks.
        single = next(
            (i for i in candidates if self.alloc.can_admit_after_release(
                need, slots[i].rid)), None)
        order = [single] if single is not None else candidates
        evicted: List[int] = []
        for victim_slot in order:
            if evicted and self.can_admit(req):
                break
            self.release(victim_slot, slots[victim_slot])
            evicted.append(victim_slot)
        return evicted

    def _touch_tables(self) -> None:
        """Invalidate the cached device table view (call after any host
        write to ``table``/``ring_table``/``ring_start``)."""
        self._tables_dev = None

    def _tables(self):
        """Device view of the host-owned block tables, cached across
        iterations. Steady-state decode mutates no table (growth touches
        one slot every ``block_len`` commits), so re-uploading every
        dispatch is pure host overhead; every mutation site invalidates
        via ``_touch_tables``.

        The upload is of a snapshot: JAX may read a host array after the
        call that takes it returns (on the CPU it can alias it), and the
        host tables are written in place while the dispatch that read
        them may still be running — uploading ``table`` itself let those
        writes race into an in-flight step."""
        if self._tables_dev is None:
            if not self.ring:
                self._tables_dev = jnp.asarray(self.table.copy())
            else:
                self._tables_dev = {
                    "full": jnp.asarray(self.table.copy()),
                    "ring": jnp.asarray(self.ring_table.copy()),
                    "start": jnp.asarray(self.ring_start.copy())}
        return self._tables_dev

    def pool_leaves(self):
        """KV pool leaves (k/v block pools + per-block scale vectors) of
        the paged cache — per-slot arenas (encdec cross K/V, positions)
        excluded."""
        out = []

        def grab(d):
            for key in ("k", "v", "kscale", "vscale"):
                if key in d:
                    out.append(d[key])

        if "stacks" in self.cache:
            for d in self.cache["stacks"]:
                grab(d)
            for d in self.cache.get("tail", []):
                grab(d)
        else:
            grab(self.cache)
        return out

    def _fit_num_blocks(self, want: int) -> int:
        """The pool size for ``num_blocks=None``: ``want`` blocks (what
        the config addresses), or fewer when the device's memory holds
        fewer. A device that reports no memory (the CPU) gets ``want``.

        The bytes the params and int8 weights leave free, less
        ``POOL_RESERVE_BYTES``, are halved: the decode step's scratch
        holds a second copy of the pool (ROADMAP, Queue 1). A block's
        bytes on the device are measured by allocating a probe pool, so
        the device's tiling counts (head_dim 96 pads to 128 lanes on a
        TPU). With a mesh, each device holds the replicated weights and
        ``1/ndev`` of every block (heads mode) or of the blocks (blocks
        mode): the same bytes per device for a pool size. Records the decision in
        ``pool_sizing``."""
        # the default device: the probe lands there, and it is the first
        # device of a serve mesh (launch.mesh.make_serve_mesh)
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        if "bytes_limit" not in stats:
            return want
        in_use, probe = stats["bytes_in_use"], 64
        pool = jax.block_until_ready(self.arch.init_paged_cache(
            self.ec.slots,
            PagedLayout(self.ec.block_len, probe, self.ec.max_len)))
        grown = dev.memory_stats()["bytes_in_use"] - in_use
        del pool
        block_bytes = max(-(-grown // (probe * self.ndev)), 1)
        free = stats["bytes_limit"] - in_use - POOL_RESERVE_BYTES
        fit = max(free, 0) // 2 // block_bytes
        if fit < 2:
            raise RuntimeError(
                f"no room for a KV pool: {in_use} B in use of "
                f"{stats['bytes_limit']} B, {block_bytes} B per block")
        self.pool_sizing = dict(
            num_blocks=int(min(want, fit)), fit_blocks=int(fit),
            want_blocks=int(want), block_bytes=int(block_bytes),
            bytes_limit=int(stats["bytes_limit"]),
            in_use_bytes=int(in_use))
        return self.pool_sizing["num_blocks"]

    @property
    def pool_bytes(self) -> int:
        """Total resident bytes of the KV block pools (full + ring arenas,
        scale vectors included) — the quantity the int8 layout halves."""
        return int(sum(leaf.nbytes for leaf in self.pool_leaves()))

    @property
    def pool_bytes_per_token(self) -> float:
        """Pool bytes per token of full-history capacity. (Ring arenas are
        counted in the numerator; for windowed models their capacity is
        window-bounded, so compare like layouts.)"""
        return self.pool_bytes / self.layout.usable_tokens

    def pool_bytes_by_device(self) -> Dict[int, int]:
        """Resident KV-pool bytes per mesh device (device index →
        bytes); without a mesh everything sits on device 0. Heads mode
        splits each pool leaf 1/ndev by head slice; blocks mode by block
        slice — either way the per-device residency is what a fixed
        per-device HBM budget constrains."""
        if self.mesh is None:
            return {0: self.pool_bytes}
        idx = {d: i for i, d in enumerate(self.mesh.devices.flat)}
        out: Dict[int, int] = {i: 0 for i in idx.values()}
        for leaf in self.pool_leaves():
            for sh in leaf.addressable_shards:
                i = idx.get(sh.device)
                if i is not None:
                    out[i] += sh.data.nbytes
        return out

    def blocks_by_device(self) -> Dict[int, int]:
        """Usable full-history blocks per device: the local slice in
        blocks mode, the whole (head-sliced) block index space
        otherwise."""
        return {d: self._dev_layout.usable_blocks for d in range(self.ndev)}

    # -- iteration hooks ---------------------------------------------------

    @hot_path
    def begin_iteration(self, active, slots, spans=None):
        """Host bookkeeping before the decode (or verify) dispatch.
        ``spans`` (speculation): per-slot write extents — slot ``i``
        writes positions ``_slot_len[i] .. _slot_len[i] + spans[i] - 1``
        this iteration (drafts + the decode position); ``None`` is the
        plain one-position decode. The engine caps each span at the
        request's remaining budget, so growth never outruns the
        admission-time block reservation."""
        blk = self.ec.block_len
        for i in active:
            req = slots[i]
            alloc = self._alloc_for(i)
            span = 1 if spans is None else spans[i]
            last_pos = self._slot_len[i] + span - 1
            if self._has_full:
                # grow any slot whose write span crosses a block boundary
                # (drawn from its admission-time reservation — can never
                # fail)
                needed = last_pos // blk + 1
                owned = alloc.owned(req.rid)
                while len(owned) < needed:
                    b = alloc.grow(req.rid)
                    self._set_table(i, len(owned), b)
                    owned.append(b)
            if self.prefix_caching:
                # publish decode blocks as they complete: a preempted (or
                # shared-prefix) continuation then re-prefills mostly from
                # cache. Position p of the slot holds K/V of seq[p], and
                # the engine appends fetched tokens before the next
                # begin_iteration, so seq always covers _slot_len.
                n_full = self._slot_len[i] // blk
                keys = self._slot_keys[i]
                if len(keys) < n_full:
                    seq = continuation_tokens(req)
                    salt = self._chain_salt(req)
                    while len(keys) < n_full:
                        idx = len(keys)
                        prev = keys[idx - 1] if idx else chain_seed(blk, salt)
                        key = chain_key(prev, seq[idx * blk:(idx + 1) * blk])
                        keys.append(key)
                        alloc.register(req.rid, idx, key)
                # copy-on-write guard: if this iteration's write span
                # lands in a block another table still references (only
                # possible after an explicit incref fork), duplicate it
                # first so the sharer's K/V stays immutable. Speculation
                # widens the span; grown blocks are fresh (never shared),
                # so the loop is a no-op past the tail in practice.
                for tail in range(self._slot_len[i] // blk,
                                  last_pos // blk + 1):
                    moved = alloc.ensure_writable(req.rid, tail)
                    if moved is not None:
                        old, new = moved
                        self.cache = self._copy_block_fn(
                            self.cache, self._block_arg(i, old),
                            self._block_arg(i, new))
                        self._set_table(i, tail, new)
            if self.ring:
                # rotate the ring table when the next write position enters
                # a block past the current ring: the evicted oldest block
                # is entirely below the window by construction
                wb = self.layout.ring_blocks
                next_bi = self._slot_len[i] // blk
                if next_bi > self._ring_first[i] + wb - 1:
                    first = next_bi - (wb - 1)
                    self._ring_first[i] = first
                    self.ring_table[i, :] = ring_table_row(
                        self._ring_ids[i], first)
                    self.ring_start[i] = first * blk
                    self._touch_tables()

    def lower_decode(self, samp, any_sampling: bool = False):
        """The decode step lowered at the current operands' shapes — what
        ``decode`` dispatches, for inspecting the program it compiles to
        (``.compile().as_text()``). Lowering donates nothing."""
        return self._decode_fn.lower(
            self.params, self.qparams, self.cache, self._tables(),
            self.last_tok, samp, any_sampling)

    @hot_path
    def decode(self, active, slots, samp, any_sampling):
        tok, self.cache = self._decode_fn(
            self.params, self.qparams, self.cache,
            self._tables(), self.last_tok, samp, any_sampling)
        self.last_tok = tok
        self.decode_dispatches += 1
        for i in active:
            self._slot_len[i] += 1
        return tok

    @hot_path
    def verify(self, active, slots, tokens, samp, any_sampling):
        """One speculative verify dispatch — the decode replacement under
        ``spec_tokens > 0``. ``tokens`` [slots, Q] carries each row's last
        committed token in column 0 and its drafts after; ``samp`` are the
        flat [slots · Q] per-position sampling vectors. Returns the chosen
        tokens [slots, Q] on device (one dispatch, fetched with the batch).
        ``_slot_len`` is *not* advanced here — the engine's acceptance
        drives :meth:`commit` per slot after the fetch."""
        packed = np.concatenate(
            [np.asarray(self._slot_len, np.int32)[:, None],
             np.asarray(tokens, np.int32)], axis=1)
        tok, self.cache = self._verify_fn(
            self.params, self.qparams, self.cache, self._tables(),
            jnp.asarray(packed), samp, any_sampling)
        self.decode_dispatches += 1
        return tok

    @hot_path
    def commit(self, slot: int, req: Request, accepted: int) -> None:
        """Commit ``accepted`` tokens from the last verify dispatch and
        roll the rejected tail back at block granularity: blocks grown
        past the new frontier are popped back to the allocator (their
        published keys retracted — recycling invariants hold every step)
        and their table entries re-point at trash. K/V written past the
        accept point inside kept blocks stays as garbage that is never
        attended and always overwritten before the frontier reaches it."""
        self._slot_len[slot] += accepted
        keep = (self._slot_len[slot] - 1) // self.ec.block_len + 1
        dropped = self._alloc_for(slot).shrink(req.rid, keep)
        if dropped:
            self.table[slot, keep:keep + len(dropped)] = 0
            self._touch_tables()

    def prefill(self, req: Request, slot: int, samp, any_sampling):
        """Reserve blocks, set up tables, and run one paged-prefill
        dispatch (K/V written straight into pool blocks); returns the
        on-device sampled first token.

        With prefix caching: the longest published chain-key prefix maps
        cached pool blocks straight into the slot's table (hits are
        increfed, never rewritten), and the dispatch runs over only the
        uncached *suffix* — the prefix K/V is gathered from the pool
        inside the jitted step. The hit is capped so at least the last
        token is always computed (its logits can't be looked up).

        This is the monolithic path: ``prefill_begin`` plus one unbounded
        ``prefill_chunk`` — the chunked admission path is the same code
        with a finite per-iteration token budget."""
        self.prefill_begin(req, slot)
        _, tok = self.prefill_chunk(req, slot, None, samp, any_sampling)
        return tok

    def prefill_begin(self, req: Request, slot: int) -> None:
        """Admission bookkeeping for a (possibly chunked) prefill: reserve
        the request's full worst-case block set, map any cached prefix
        hit, and set the chunk cursor. No dispatch happens here — chunks
        are dispatched by ``prefill_chunk``; a cache hit simply shortens
        the chunk list (the cursor starts past the mapped prefix)."""
        blk = self.ec.block_len
        toks = continuation_tokens(req)
        n = toks.size
        pre_len = self._pre_len(req)
        now_blocks = pre_len // blk if self._has_full else 0
        alloc = self._alloc_for(slot)
        j = 0
        keys_full: List[bytes] = []
        if self.prefix_caching:
            keys_full = self._chain_keys(req)
            j = len(alloc.lookup(keys_full[:self._hit_limit(req)]))
        block_ids = np.asarray(
            alloc.admit(req.rid, now_blocks,
                        self._max_blocks_needed(req),
                        keys=keys_full[:j]),
            np.int32)
        # the table row stays zeroed until the *final* chunk completes: a
        # mid-chunk slot is excluded from the decode active set, but the
        # batched decode still computes its (garbage) row each iteration —
        # a zeroed table diverts that row's K/V write into the trash block
        # instead of the partially-written admission blocks
        if self.kv_mode == "blocks":
            self.table[:, slot, :] = 0
        else:
            self.table[slot, :] = 0
        self._touch_tables()
        ring_ids = None
        if self.ring:
            wb = self.layout.ring_blocks
            try:
                ring_ids = np.asarray(
                    self.ring_alloc.admit(req.rid, wb, wb), np.int32)
            except Exception:
                # admission is all-or-nothing: a failed ring reservation
                # must hand the full-history reservation back, or its
                # blocks leak from the pool until reset (found by the
                # alloc-pairing checker)
                alloc.release(req.rid)
                raise
            first = max(0, (n - 1) // blk - (wb - 1))
            self._ring_first[slot] = first
            self._ring_ids[slot] = ring_ids
            self.ring_table[slot, :] = ring_table_row(ring_ids, first)
            self.ring_start[slot] = first * blk
        self._key_memo.pop(req.rid, None)
        req.prefill_pos = j * blk
        self.prefill_tokens_total += j * blk
        self.prefill_tokens_skipped += j * blk
        self._chunk[slot] = dict(toks=toks, n=n, pre_len=pre_len,
                                 block_ids=block_ids, keys=keys_full,
                                 ring_ids=ring_ids)

    @hot_path
    def prefill_chunk(self, req: Request, slot: int, budget, samp,
                      any_sampling):
        """One prefill-chunk dispatch for the admission started by
        ``prefill_begin``. ``budget`` bounds this chunk's token count
        (``None`` → the whole remaining suffix, the monolithic path).
        Returns ``(tokens_consumed, tok)`` where ``tok`` is the sampled
        first-token device array on the *final* chunk and ``None``
        mid-prefill (a mid-chunk's sampled token is garbage: the true
        next token is the prompt itself).

        Chunk boundaries land on block boundaries (mid-chunks are exact
        block multiples), so every chunk writes whole pool blocks with
        ``start`` at the cursor and gathers the already-written blocks as
        its prefix — the identical suffix-resume path a prefix-cache hit
        uses, hence token-identical to the monolithic dispatch. Returns
        ``(0, None)`` without dispatching when the budget is under one
        block (the engine counts a stall)."""
        st = self._chunk[slot]
        blk = self.ec.block_len
        alloc = self._alloc_for(slot)
        c = req.prefill_pos
        n = st["n"]
        rem = n - c
        if budget is None or budget >= rem:
            length = rem
            final = True
        else:
            length = (budget // blk) * blk
            if length <= 0:
                return 0, None
            final = False
        toks = st["toks"]
        block_ids = st["block_ids"]
        pb = c // blk                      # resume depth in blocks
        if final and self._bucketing:
            if budget is None:
                # monolithic-compatible padding: the full admission bucket
                width = st["pre_len"] - c
            else:
                # budgeted final chunk: pad only to the chunk-local pow2
                # bucket (block-rounded, never past the reservation) so a
                # short tail doesn't cost a full-bucket dispatch
                bucket = bucket_for(length, max(self.ec.min_bucket, blk),
                                    max(budget, blk))
                width = min(blocks_for(bucket, blk) * blk,
                            st["pre_len"] - c)
            padded = np.zeros((1, width), np.int32)
            padded[0, :length] = toks[c:]
            tokens = jnp.asarray(padded)
            true_len = jnp.asarray(n, jnp.int32)
        else:
            # exact tokens, no pad: mid-chunks always (fixed shape per
            # chunk size × resume depth) and every chunk on non-bucketing
            # archs (MoE routing capacity depends on token count); K/V
            # writes pad to block granularity internally
            width = length
            tokens = jnp.asarray(toks[c:c + length][None, :])
            true_len = None
        embeds = None if req.embeds is None else jnp.asarray(req.embeds)[None]
        suffix_ids = block_ids[pb:blocks_for(c + width, blk)]
        if self.kv_mode == "blocks":
            # owner plane holds the real local ids; other devices write
            # (and gather prefixes) through 0 → their local trash block
            dev = self._dev(slot)
            bid = np.zeros((self.ndev, suffix_ids.size), np.int32)
            bid[dev] = suffix_ids
            bid_arg = jnp.asarray(bid)
            prefix_ids = None
            if pb:
                pid = np.zeros((self.ndev, pb), np.int32)
                pid[dev] = block_ids[:pb]
                prefix_ids = jnp.asarray(pid)
        else:
            bid_arg = jnp.asarray(suffix_ids)
            prefix_ids = jnp.asarray(block_ids[:pb]) if pb else None
        ring_ids = st["ring_ids"]
        # start=c is static: one trace per (chunk width, resume depth)
        tok, self.cache, self.last_tok = self._prefill_fn(
            self.params, tokens, true_len, jnp.asarray(slot, jnp.int32),
            bid_arg,
            None if ring_ids is None else jnp.asarray(ring_ids),
            self.cache, self.last_tok, samp, embeds, prefix_ids,
            any_sampling, c)
        # monolithic mode runs through this same path as one unbudgeted
        # chunk; only budgeted (chunking-active) dispatches count, so the
        # metric reads 0 on monolithic/ring engines
        if self.chunking:
            self.prefill_chunk_dispatches += 1
        self.prefill_tokens_total += length
        end = c + length
        req.prefill_pos = end
        if self.prefix_caching:
            # publish every freshly written full block under its chain key
            # as its chunk completes (first-wins on key collision: the
            # duplicate stays private) — a concurrent admission can hit a
            # mid-flight request's finished blocks
            for idx in range(pb, end // blk):
                alloc.register(req.rid, idx, st["keys"][idx])
        if not final:
            return length, None
        # final chunk: the slot becomes a decode row — fill its table from
        # the admitted blocks and hand the per-slot chain keys over to the
        # decode-block publishing path
        if self.kv_mode == "blocks":
            self.table[self._dev(slot), slot, :block_ids.size] = block_ids
        else:
            self.table[slot, :block_ids.size] = block_ids
        self._touch_tables()
        self._slot_len[slot] = n
        if self.prefix_caching:
            self._slot_keys[slot] = list(st["keys"][:n // blk])
        del self._chunk[slot]
        return length, tok


_BACKENDS = {
    "slot": SlotBackend,
    "arena": ArenaBackend,
    "paged": PagedBackend,
}

# config.BACKENDS is the single source of truth for valid names
# (EngineConfig canonicalizes + validates at construction); this dispatch
# table must cover it exactly — drift fails at import, not at serve time
from repro.serve.config import BACKENDS as _NAMES  # noqa: E402

if set(_BACKENDS) != set(_NAMES):
    raise ImportError(
        f"backend registry drift: config.BACKENDS={_NAMES} vs "
        f"dispatch table {tuple(_BACKENDS)}")


def make_backend(name: str, arch: registry.Arch, params,
                 ec: EngineConfig, mesh=None) -> _BackendBase:
    try:
        cls = _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown serve backend {name!r} "
            f"(supported: {', '.join(_NAMES)})") from None
    if mesh is not None:
        if cls is not PagedBackend:
            raise ValueError(
                f"mesh-sharded serving is paged-only; backend {name!r} has "
                f"no sharded KV layout — use backend='paged'")
        return cls(arch, params, ec, mesh=mesh)
    return cls(arch, params, ec)
