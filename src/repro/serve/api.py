"""``LLMEngine`` — the one serve front-end.

Construction names the execution backend and the admission policy; there
is exactly one engine class::

    from repro.serve import EngineConfig, LLMEngine

    eng = LLMEngine(arch, params,
                    EngineConfig(backend="paged", scheduler="qos"))
    h = eng.add_request(prompt, max_new_tokens=32, qos="rt",
                        stop_sequences=[[13, 13]], eos_token=2)
    for out in eng.stream(h):        # steps the engine until h finishes
        print(out.token, out.finish_reason)
    eng.abort(h)                     # from anywhere: frees the slot AND
                                     # returns its pool blocks immediately

The engine owns queue + slots + lifecycle (``serve.request``), delegates
*when/who to admit or preempt* to a :class:`~repro.serve.scheduler
.Scheduler`, and *where KV lives / how tokens are computed* to a
:class:`~repro.serve.backends.CacheBackend`. One engine iteration
(``step()``) keeps the QoS dataflow contract of the vectorized backends:
exactly one batched decode dispatch, at most ``admit_batch`` admission
prefill dispatches, one device→host token fetch — stop-sequence / EOS /
length finishes are host-side checks riding that single fetch.

The legacy classes (``ServeEngine``, ``BatchedServeEngine``,
``PagedServeEngine`` in ``repro.serve.engine``) are thin deprecation
shims over this class and stay token-identical to it.
"""

from __future__ import annotations

import functools
import time
from collections import deque
from typing import Dict, Iterator, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.annotations import hot_path
from repro.models import registry
from repro.serve import tracing
from repro.serve.backends import make_backend
from repro.serve.config import EngineConfig
from repro.serve.request import (
    FinishReason, Request, RequestState, StepOutput, normalize_stop_sequences,
)
from repro.serve.scheduler import QOS_CLASSES, Scheduler, make_scheduler

Handle = int

#: iterations whose wall time and committed tokens ``metrics()`` keeps (a
#: rolling window: a long-running server keeps no more than this)
ITER_WINDOW = 2048


def _sampling_span(build):
    """Run a sampling-vector builder inside an ``engine.sampling`` span
    that records whether any row samples."""
    @functools.wraps(build)
    def traced(self, *args):
        with tracing.span(tracing.ENGINE_SAMPLING) as sp:
            vecs, any_sampling = build(self, *args)
            sp.set_metadata(sampling=int(any_sampling))
        return vecs, any_sampling
    return traced


class LLMEngine:
    """Continuous-batching serve engine with pluggable scheduler/backend."""

    def __init__(self, arch: registry.Arch, params,
                 config: Optional[EngineConfig] = None, *,
                 backend=None, scheduler: Optional[Scheduler] = None,
                 mesh=None):
        """``backend`` / ``scheduler`` inject pre-built instances (any
        object honoring the ``CacheBackend`` / ``Scheduler`` protocols —
        how the scheduler unit tests run against a fake backend);
        normally both are constructed from ``config``. ``mesh`` (a
        ``jax.sharding.Mesh`` with a ``model`` axis, e.g. from
        ``launch.mesh.make_serve_mesh``) shards the paged KV pool across
        its devices — paged backend only."""
        ec = config if config is not None else EngineConfig()
        # (admit_batch/scheduler/backend-name validation lives in
        # EngineConfig.__post_init__; only the cross-field check that
        # depends on the shim-pinned backend happens here)
        if ec.attn_backend is not None and ec.backend != "paged":
            raise ValueError(
                f"attn_backend={ec.attn_backend!r} applies to the paged "
                f"backend only — the dense-arena backends do not dispatch "
                f"through kernels.paged_attention")
        if ec.prefill_chunk_tokens is not None and ec.backend != "paged":
            raise ValueError(
                f"prefill_chunk_tokens={ec.prefill_chunk_tokens} applies "
                f"to the paged backend only — chunked prefill resumes at "
                f"block boundaries of the shared pool, which the dense "
                f"arenas don't have")
        if ec.spec_tokens > 0 and ec.backend != "paged":
            raise ValueError(
                f"spec_tokens={ec.spec_tokens} applies to the paged "
                f"backend only — draft verification writes through the "
                f"block pools and rollback rides the paged allocator")
        if mesh is not None and backend is not None:
            raise ValueError(
                "pass the mesh to the injected backend's constructor — "
                "LLMEngine(mesh=...) only applies when it builds the "
                "backend itself")
        self.arch = arch
        self.ec = ec
        self.params = params
        self.scheduler: Scheduler = (scheduler if scheduler is not None
                                     else make_scheduler(ec))
        self.backend = (backend if backend is not None
                        else make_backend(ec.backend, arch, params, ec,
                                          mesh=mesh))
        self.queue: deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * ec.slots
        self.iterations = 0
        self.max_concurrent = 0           # peak active slots (capacity proof)
        # chunked prefill: active iff the config asks for it AND the
        # backend supports it (rings opt out backend-side; injected fakes
        # default to monolithic)
        self._chunked = (ec.prefill_chunk_tokens is not None
                         and getattr(self.backend, "chunking", False))
        self._chunk_stalls = 0   # chunk/admission dispatches deferred by
        #                          an exhausted per-iteration token budget
        # speculative decoding: active iff configured AND the backend
        # supports it (rings / mesh-sharded pools opt out backend-side —
        # the same silent-fallback contract as chunked prefill; a
        # non-paged backend raised above). Greedy acceptance keeps the
        # committed stream token-identical to spec off.
        self._spec = (ec.spec_tokens
                      if getattr(self.backend, "spec_supported", False)
                      else 0)
        self.spec_drafted = 0    # draft tokens proposed across iterations
        self.spec_accepted = 0   # drafts accepted (excludes bonus tokens)
        # per-iteration wall clock over the last ITER_WINDOW iterations:
        # decode-iteration jitter = p99 − p50 over this window, the number
        # chunked prefill exists to bound. _iter_tokens rides alongside
        # (same window): committed tokens per iteration, so walls can be
        # normalized per-token — a speculative iteration commits several.
        self._iter_walls: deque = deque(maxlen=ITER_WINDOW)
        self._iter_tokens: deque = deque(maxlen=ITER_WINDOW)
        # all-greedy dispatches ignore the sampling operands entirely
        # (static any_sampling=False compiles to argmax), so one cached
        # zero vector set per length replaces four host→device uploads
        # every iteration
        self._greedy_vecs: Dict[int, tuple] = {}
        self._requests: Dict[int, Request] = {}
        # finished handles in completion order — the pruning queue when
        # ec.retain_finished bounds the registry (long-running servers)
        self._finished_order: deque[int] = deque()
        self._next_rid = 0

    # Legacy observability (decode_dispatches, transfers, traces) and the
    # backend-specific surface (alloc, layout, ring tables, pool_bytes,
    # qparams, cache, ...) live on the backend; delegate reads so both the
    # deprecation shims and existing benchmarks keep working unchanged.
    def __getattr__(self, name):
        backend = self.__dict__.get("backend")
        if backend is not None and hasattr(backend, name):
            return getattr(backend, name)
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")

    def _choose_slot(self, req, avail):
        # injected backends (protocol implementers, test fakes) may not
        # define choose_slot; the default placement is first-available
        chooser = getattr(self.backend, "choose_slot", None)
        if chooser is None:
            return avail[0] if avail else None
        return chooser(req, avail)

    # -- request intake ----------------------------------------------------

    def add_request(self, prompt, *, max_new_tokens: int = 16,
                    qos: str = "be", temperature: Optional[float] = None,
                    top_k: int = 0,
                    stop_sequences=None, eos_token: Optional[int] = None,
                    embeds: Optional[np.ndarray] = None,
                    rid: Optional[int] = None) -> Handle:
        """Queue a generation request; returns its handle (the rid)."""
        if rid is None:
            rid = self._next_rid
        req = Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens, qos=qos,
                      temperature=temperature, top_k=top_k,
                      stop_sequences=stop_sequences, eos_token=eos_token,
                      embeds=embeds)
        return self.submit(req)

    def submit(self, req: Request) -> Handle:
        """Queue a fully-built :class:`Request`; returns its handle."""
        if len(req.prompt) + req.max_new_tokens > self.ec.max_len:
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds "
                f"max_len={self.ec.max_len}")
        if req.qos not in QOS_CLASSES:
            raise ValueError(
                f"request {req.rid}: unknown qos class {req.qos!r} "
                f"(supported: {', '.join(QOS_CLASSES)})")
        live = self._requests.get(req.rid)
        if live is not None and not live.finished and live is not req:
            raise ValueError(
                f"request id {req.rid} is already live on this engine")
        if live is not None and live.finished:
            # rid reuse: drop the finished predecessor's retention entry,
            # or a later prune would pop it against the *new* occupant —
            # each rid appears at most once in the finished order
            try:
                self._finished_order.remove(req.rid)
            except ValueError:
                pass
        req.stop_sequences = normalize_stop_sequences(req.stop_sequences)
        self.backend.validate_request(req)
        req.state = RequestState.WAITING
        req.waiting_iters = 0
        req.submitted_at = time.perf_counter()
        self.queue.append(req)
        self._requests[req.rid] = req
        self._next_rid = max(self._next_rid, req.rid + 1)
        return req.rid

    def request(self, handle: Union[Handle, Request]) -> Request:
        if isinstance(handle, Request):
            return handle
        try:
            return self._requests[handle]
        except KeyError:
            raise KeyError(f"unknown request handle {handle!r}") from None

    # -- lifecycle ---------------------------------------------------------

    @property
    def idle(self) -> bool:
        return not self.queue and all(r is None for r in self.slots)

    def abort(self, handle: Union[Handle, Request]) -> bool:
        """Abort a request wherever it is. Waiting/preempted requests
        leave the queue; a running request's slot is vacated and — on the
        paged backend — its full-arena *and* ring-arena blocks return to
        the allocators immediately. Returns False if it already finished.
        """
        req = self.request(handle)
        if req.finished:
            return False
        if req in self.queue:
            self.queue.remove(req)
            # never admitted (or preempted out of its slot): no release()
            # will run for this rid, so drop backend per-rid memo state
            # here — a reused rid must not inherit stale chain keys
            self._backend_forget(req)
        else:
            for i, r in enumerate(self.slots):
                if r is req:
                    self.backend.release(i, req)
                    self.slots[i] = None
                    break
        req.state = RequestState.ABORTED
        req.finish_reason = FinishReason.ABORT
        req.done_at = time.perf_counter()
        self._note_finished(req)
        return True

    def _note_finished(self, req: Request) -> None:
        """Record completion; with ``ec.retain_finished`` set, drop the
        oldest finished handles so the registry stays bounded in a
        long-running serve loop."""
        self._finished_order.append(req.rid)
        keep = self.ec.retain_finished
        if keep is None:
            return
        while len(self._finished_order) > keep:
            old = self._finished_order.popleft()
            stale = self._requests.get(old)
            if stale is not None and stale.finished:
                del self._requests[old]

    # -- sampling vectors (vectorized backends) ----------------------------

    def _req_temperature(self, req: Request) -> float:
        """Effective decode temperature (``ec.effective_temperature``)."""
        return self.ec.effective_temperature(req.temperature)

    @_sampling_span
    def _sampling_vectors(self):
        """(per-slot (temps, topks, rids, steps), any_sampling) for this
        iteration's decode dispatch. Empty slots sample greedily into
        garbage rows that are ignored host-side; ``steps`` is each
        request's output-token index (the stateless-PRNG coordinate).
        ``any_sampling`` is the static hot-path switch: False (the common
        all-greedy case) compiles to a plain argmax."""
        n = self.ec.slots
        if self._all_greedy():
            return self._greedy_sampling_vectors(n), False
        temps = np.zeros((n,), np.float32)
        topks = np.zeros((n,), np.int32)
        rids = np.zeros((n,), np.int32)
        steps = np.zeros((n,), np.int32)
        for i, r in enumerate(self.slots):
            if r is None:
                continue
            temps[i] = self._req_temperature(r)
            topks[i] = r.top_k
            rids[i] = r.rid
            steps[i] = len(r.output)
        vecs = (jnp.asarray(temps), jnp.asarray(topks),
                jnp.asarray(rids), jnp.asarray(steps))
        return vecs, bool(temps.max(initial=0.0) > 0)

    def _all_greedy(self) -> bool:
        """True when no occupied slot samples (every row decodes via the
        static greedy path, which never reads the sampling operands)."""
        return not any(r is not None and self._req_temperature(r) > 0
                       for r in self.slots)

    def _greedy_sampling_vectors(self, n: int):
        """Cached constant zero sampling vectors of length ``n`` — the
        operand payload for ``any_sampling=False`` dispatches, whose
        compiled body is a plain argmax that ignores them."""
        vecs = self._greedy_vecs.get(n)
        if vecs is None:
            zi = jnp.zeros((n,), jnp.int32)
            vecs = (jnp.zeros((n,), jnp.float32), zi, zi, zi)
            self._greedy_vecs[n] = vecs
        return vecs

    @_sampling_span
    def _admission_vectors(self, req: Request):
        """(length-1 sampling vectors, any_sampling) for an admission
        prefill's first token (same stateless coordinates as decode)."""
        temp = self._req_temperature(req)
        vecs = (jnp.asarray([temp], jnp.float32),
                jnp.asarray([req.top_k], jnp.int32),
                jnp.asarray([req.rid], jnp.int32),
                jnp.asarray([len(req.output)], jnp.int32))
        return vecs, temp > 0

    # -- speculative decoding ----------------------------------------------

    def _build_drafts(self, active):
        """Token matrix + spans + per-slot drafts for one verify dispatch.

        Row ``i`` of the [slots, k+1] matrix is the slot's last committed
        token followed by its n-gram drafts; unused columns stay 0 — their
        K/V writes land past the frontier (or in trash) and their logits
        are ignored host-side. Each slot's draft count is capped at
        ``remaining - 1`` so a commit can never exceed ``max_new_tokens``
        (nor outgrow the admission-time block reservation); a slot on its
        final token drafts nothing and behaves exactly like plain decode.
        ``spans[i] = drafts + 1`` is the slot's write extent for
        ``begin_iteration``.
        """
        from repro.serve.backends import continuation_tokens
        from repro.serve.spec import ngram_propose

        k = self._spec
        mat = np.zeros((self.ec.slots, k + 1), np.int32)
        spans = [1] * self.ec.slots
        drafts: Dict[int, List[int]] = {}
        for i in active:
            r = self.slots[i]
            mat[i, 0] = r.output[-1]
            cap = min(k, r.max_new_tokens - len(r.output) - 1)
            d = ngram_propose(continuation_tokens(r), cap) if cap > 0 else []
            mat[i, 1:1 + len(d)] = d
            spans[i] = len(d) + 1
            drafts[i] = d
            self.spec_drafted += len(d)
        return mat, spans, drafts

    @_sampling_span
    def _verify_sampling_vectors(self):
        """Flat [slots · (k+1)] sampling vectors for a verify dispatch:
        entry ``i·Q + j`` carries slot ``i``'s coordinates with ``steps``
        at the *absolute* output index ``len(output) + j`` of the token
        position ``j`` would commit. Keying the stateless PRNG by absolute
        index (not iteration count) is what makes a sampled request's
        token sequence identical with speculation on or off — position
        ``p`` draws the same key either way."""
        n, q = self.ec.slots, self._spec + 1
        if self._all_greedy():
            return self._greedy_sampling_vectors(n * q), False
        temps = np.zeros((n * q,), np.float32)
        topks = np.zeros((n * q,), np.int32)
        rids = np.zeros((n * q,), np.int32)
        steps = np.zeros((n * q,), np.int32)
        for i, r in enumerate(self.slots):
            if r is None or r.state != RequestState.RUNNING:
                continue
            lo = i * q
            temps[lo:lo + q] = self._req_temperature(r)
            topks[lo:lo + q] = r.top_k
            rids[lo:lo + q] = r.rid
            steps[lo:lo + q] = len(r.output) + np.arange(q)
        vecs = (jnp.asarray(temps), jnp.asarray(topks),
                jnp.asarray(rids), jnp.asarray(steps))
        return vecs, bool(temps.max(initial=0.0) > 0)

    # -- one iteration -----------------------------------------------------

    def _dispatch_admission(self, req: Request, slot: int, budget=None):
        """One admission dispatch for ``req`` into ``slot``. Monolithic
        backends run the whole prefill; under chunked prefill only the
        first chunk (within ``budget`` tokens) is dispatched and the
        request stays in PREFILL until later iterations finish it.
        Returns ``(tokens_consumed, tok_or_None)``."""
        now = time.perf_counter()
        if req.admitted_at is None:
            req.admitted_at = now
        with tracing.span(tracing.ENGINE_ADMIT, rid=req.rid,
                          prompt_tokens=len(req.prompt),
                          waited_us=int((now - req.submitted_at) * 1e6)):
            req.state = RequestState.PREFILL
            req.waiting_iters = 0
            if self.backend.vectorized:
                samp, any_sampling = self._admission_vectors(req)
            else:
                samp, any_sampling = None, False
            if self._chunked:
                self.backend.prefill_begin(req, slot)
                self.slots[slot] = req
                return self.backend.prefill_chunk(req, slot, budget, samp,
                                                  any_sampling)
            tok = self.backend.prefill(req, slot, samp, any_sampling)
            self.slots[slot] = req
            return 0, tok

    def step(self) -> List[StepOutput]:
        """One engine iteration → every request's progress this step."""
        outputs, _ = self._step()
        return outputs

    @hot_path
    def _step(self):
        """One engine iteration. Exactly one decode pass (if any slot is
        active), up to ``admit_batch`` admission dispatches (plus at most
        one forced admission), then a single device→host fetch of the
        sampled tokens. Under chunked prefill the iteration is *bounded*:
        all prefill work (chunk continuations first, then new admissions)
        shares one ``prefill_chunk_tokens`` token budget, so a long
        prompt can no longer stall every running decode behind a
        monolithic dispatch. Every finish condition is a host-side check on
        that fetch. Which requests finish *by length* is known before the
        fetch, so their resources are recycled in time for this
        iteration's admissions; stop/EOS finishes release on the fetch.
        """
        self.iterations += 1
        it_t0 = time.perf_counter()
        outputs: List[StepOutput] = []
        # decode batches only RUNNING occupants; mid-chunk (PREFILL-state)
        # slots hold blocks but have no tokens yet — their prefill
        # continues below, inside this same bounded iteration
        active = [i for i, r in enumerate(self.slots)
                  if r is not None and r.state == RequestState.RUNNING]
        chunking = [i for i, r in enumerate(self.slots)
                    if r is not None and r.state == RequestState.PREFILL]
        with tracing.span(tracing.ENGINE_STEP, iteration=self.iterations,
                          running=len(active), chunking=len(chunking),
                          queued=len(self.queue)) as sp:
            if tracing.enabled():
                sp.set_metadata(written_tokens=self._written_tokens(active),
                                reserved_blocks=self._reserved_blocks())
            finished = self._iterate(active, chunking, outputs)
        self._iter_walls.append(time.perf_counter() - it_t0)
        self._iter_tokens.append(
            sum(1 for o in outputs if o.token is not None))
        return outputs, finished

    def _written_tokens(self, active) -> int:
        """Sequence lengths of the decode rows ``active``."""
        return sum(len(self.slots[i].prompt) + len(self.slots[i].output)
                   for i in active)

    def _reserved_blocks(self) -> int:
        """Pool blocks held or reserved by admitted requests (0 on a
        backend without a block pool)."""
        fn = getattr(self.backend, "reserved_blocks", None)
        return fn() if fn is not None else 0

    @hot_path
    def _iterate(self, active, chunking, outputs) -> List[Request]:
        """The body of ``_step``: decode (or verify) dispatch, chunk
        continuations, admissions, then the fetch. Appends this
        iteration's outputs to ``outputs``; returns the requests that
        finished."""
        at_dispatch = list(self.slots)  # snapshot: who owns each decode row
        self.max_concurrent = max(self.max_concurrent,
                                  len(active) + len(chunking))
        # speculative path: build drafts host-side and replace the decode
        # dispatch with one small-q verify over [slots, k+1] positions —
        # still exactly one batched dispatch and one fetch per iteration
        spec_drafts = None
        spec_mat = spec_spans = None
        if self._spec and active:
            spec_mat, spec_spans, spec_drafts = self._build_drafts(active)
        if spec_spans is not None:
            self.backend.begin_iteration(active, self.slots,
                                         spans=spec_spans)
        else:
            self.backend.begin_iteration(active, self.slots)

        dec_tok = None
        if active:
            if spec_drafts is not None:
                samp, any_sampling = self._verify_sampling_vectors()
                dec_tok = self.backend.verify(
                    active, self.slots, spec_mat, samp, any_sampling,
                    drafted=sum(map(len, spec_drafts.values())))
            elif self.backend.vectorized:
                samp, any_sampling = self._sampling_vectors()
                dec_tok = self.backend.decode(active, self.slots, samp,
                                              any_sampling)
            else:
                dec_tok = self.backend.decode(active, self.slots, None,
                                              False)

        # chunked prefill: continue in-flight admissions first (they
        # already hold their blocks, and finishing one turns a dead slot
        # into a decode row). The per-iteration token budget is shared —
        # the QoS scheduler drains it into rt chunks before be
        # (chunk_order), realizing "rt prefill outranks be work".
        admitted: List[tuple] = []      # (request, slot, first token)
        budget = self.ec.prefill_chunk_tokens if self._chunked else None
        if chunking:
            pairs = [(i, self.slots[i]) for i in chunking]
            order_fn = getattr(self.scheduler, "chunk_order", None)
            order = (order_fn(pairs) if order_fn is not None
                     else [i for i, _ in pairs])
            for i in order:
                if budget is not None and budget < self.ec.block_len:
                    self._chunk_stalls += 1
                    break
                r = self.slots[i]
                samp, any_sampling = self._admission_vectors(r)
                used, tok = self.backend.prefill_chunk(r, i, budget, samp,
                                                       any_sampling)
                if budget is not None:
                    budget -= used
                if tok is not None:
                    admitted.append((r, i, tok))

        # length-determined finishes free their resources *now* so this
        # iteration's admissions can reuse them (the decode dispatch that
        # read them is already ordered before any insert)
        will_free = [i for i in active
                     if len(self.slots[i].output) + 1
                     >= self.slots[i].max_new_tokens]
        for i in will_free:
            self.backend.release(i, self.slots[i])
        pre_released = set(will_free)
        free = [i for i, r in enumerate(self.slots) if r is None]
        with tracing.span(tracing.ENGINE_SCHEDULE) as sp:
            granted, blocked = self._schedule(free + will_free, budget,
                                              pre_released, admitted)
            sp.set_metadata(admitted=len(granted), blocked=int(blocked))

        finished = self._fetch_and_finish(dec_tok, active, at_dispatch,
                                          admitted, pre_released, outputs,
                                          spec_drafts)
        # only *dispatched* admissions accrue scheduler credit (a chunked
        # admission counts from its first chunk; a deferred forced
        # admission counts nothing — see Scheduler.note_iteration)
        self.scheduler.note_iteration(granted, list(self.queue))
        return finished

    @hot_path
    def _schedule(self, avail, budget, pre_released, admitted):
        """Admissions into the slots ``avail`` (free or freeing this
        iteration) within the chunk ``budget``, then forced admission and
        preemption. Appends each admission whose first token is in flight
        to ``admitted``; returns ``(granted, blocked)``: the dispatched
        admissions, and whether the admission pass stopped on capacity."""
        granted: List[Request] = []     # dispatched admissions (for credit)
        granted_slots: set = set()
        blocked = False
        # scheduler-ordered admissions into free (or freeing) slots; stop
        # at the first capacity-blocked request (head-of-line credit —
        # an exhausted chunk budget blocks the head the same way)
        limit = min(self.ec.admit_batch,
                    self.backend.max_admit or self.ec.admit_batch)
        for req in self.scheduler.admit_order(list(self.queue)):
            if not avail or len(granted) >= limit:
                break
            if budget is not None and budget < self.ec.block_len:
                self._chunk_stalls += 1
                break
            if not self.backend.can_admit(req):
                blocked = True
                break
            # the backend picks *which* free slot (block-sharded paged
            # serving pins slots to devices; None = capacity exists but
            # no listed slot's device can take the request — same
            # head-of-line credit as a capacity block)
            slot = self._choose_slot(req, avail)
            if slot is None:
                blocked = True
                break
            avail.remove(slot)
            self.queue.remove(req)
            used, tok = self._dispatch_admission(req, slot, budget)
            if budget is not None:
                budget -= used
            granted.append(req)
            granted_slots.add(slot)
            if tok is not None:
                admitted.append((req, slot, tok))

        # forced admission (bounded-priority / QoS rt guarantee): a slot
        # still free after the admission pass is used first — the
        # guarantee outranks the admit_batch cap (and, chunked, gets a
        # fresh one-chunk allowance: the latency bound outranks the
        # shared budget, overshooting it by at most one chunk), and
        # evicting a running request while a slot sits empty would throw
        # its KV away for no capacity reason. Only then preempt victims —
        # never a slot that is finishing or was admitted this iteration —
        # until the forced request fits.
        forced_budget = (self.ec.prefill_chunk_tokens if self._chunked
                         else None)
        forced = self.scheduler.forced_request(list(self.queue), granted)
        if forced is not None and self.backend.can_admit(forced):
            slot = self._choose_slot(forced, avail)
            if slot is not None:
                avail.remove(slot)
                self.queue.remove(forced)
                used, tok = self._dispatch_admission(forced, slot,
                                                     forced_budget)
                granted.append(forced)
                granted_slots.add(slot)
                if tok is not None:
                    admitted.append((forced, slot, tok))
                forced = None
        if forced is not None:
            # never evict a slot admitted this iteration, nor one whose
            # final chunk just completed (its first token is in flight —
            # _fetch_and_finish would resurrect a preempted request)
            taken = granted_slots | {s for _, s, _ in admitted}
            running = [(i, r) for i, r in enumerate(self.slots)
                       if r is not None and i not in pre_released
                       and i not in taken]
            if running:
                candidates = self.scheduler.victim_order(running)
                evict = self.backend.evict_for(forced, candidates,
                                               self.slots)
                victims: List[Request] = []
                for s in evict:
                    v = self.slots[s]
                    v.preemptions += 1
                    v.state = RequestState.PREEMPTED
                    v.waiting_iters = 0
                    self.slots[s] = None
                    victims.append(v)
                if victims:
                    for v in reversed(victims):
                        self.queue.appendleft(v)  # re-admitted at queue head
                    # re-check capacity post-eviction: evict_for's
                    # feasibility check makes this always true today, but
                    # a dispatch on a stale answer would raise out of
                    # step() with the request half-admitted — never risk it
                    if self.backend.can_admit(forced):
                        self.queue.remove(forced)
                        slot = evict[0]
                        used, tok = self._dispatch_admission(forced, slot,
                                                             forced_budget)
                        granted.append(forced)
                        granted_slots.add(slot)
                        if tok is not None:
                            admitted.append((forced, slot, tok))
        return granted, blocked

    # -- fetch + host-side finish bookkeeping ------------------------------

    def _backend_forget(self, req: Request) -> None:
        # injected backends (protocol implementers, test fakes) may not
        # define the forget hook
        fn = getattr(self.backend, "forget", None)
        if fn is not None:
            fn(req)

    def _finish(self, req: Request, slot: Optional[int], reason: str,
                now: float, already_released: bool,
                finished: List[Request]) -> None:
        req.finish_reason = reason
        req.state = RequestState.DONE
        req.done_at = now
        if slot is not None:
            if not already_released:
                self.backend.release(slot, req)
            if self.slots[slot] is req:
                self.slots[slot] = None
        else:
            # finishing without a slot (a preempted victim completing on
            # its pre-eviction token): release() never runs for this rid —
            # invalidate backend per-rid memo state explicitly
            self._backend_forget(req)
        self._note_finished(req)
        finished.append(req)

    @hot_path
    def _fetch_and_finish(self, dec_tok, active, at_dispatch, admitted,
                          pre_released, outputs,
                          spec_drafts=None) -> List[Request]:
        """One async device→host fetch of this iteration's sampled tokens
        (decode batch + every admitted request's first token), then the
        host-side finish bookkeeping: stop sequences, EOS, length.

        ``admitted`` is this iteration's admission list — ``(request, slot,
        first token)`` triples. ``spec_drafts`` (speculation) maps slot →
        its proposed draft list; ``dec_tok`` is then the [slots, Q] verify
        choices, greedy acceptance commits the longest agreeing prefix per
        slot (plus the bonus token), and the backend rolls rejected-draft
        blocks back. Every committed position is scanned for finishes —
        a stop/EOS match truncates the accepted tail behind it.
        """
        finished: List[Request] = []
        if self.backend.vectorized:
            fetch = {}
            if dec_tok is not None:
                fetch["dec"] = dec_tok
            if admitted:
                fetch["adm"] = [tok for _, _, tok in admitted]
            if not fetch:
                return finished
            with tracing.span(tracing.ENGINE_FETCH,
                              arrays=int(dec_tok is not None)
                              + len(admitted)):
                jax.tree.map(lambda a: a.copy_to_host_async(), fetch)
                # repro: allow(host-sync) -- the contract's single fetch
                # per iteration (async-started above, batched across the
                # slots)
                got = jax.device_get(fetch)
            self.backend.transfers += 1
            dec_vals = got.get("dec")
            adm_vals = got.get("adm", [])
        else:
            if dec_tok is None and not admitted:
                return finished
            dec_vals = dec_tok                     # {slot: host int}
            adm_vals = [tok for _, _, tok in admitted]
        n_out = len(outputs)
        with tracing.span(tracing.ENGINE_FINISH) as sp:
            self._commit_tokens(dec_vals, adm_vals, active, at_dispatch,
                                admitted, pre_released, outputs,
                                spec_drafts, finished)
            sp.set_metadata(finished=len(finished),
                            tokens=len(outputs) - n_out)
        return finished

    @hot_path
    def _commit_tokens(self, dec_vals, adm_vals, active, at_dispatch,
                       admitted, pre_released, outputs, spec_drafts,
                       finished) -> None:
        """Host-side finish bookkeeping on the fetched tokens: commit
        each row's tokens (greedy acceptance under speculation), check
        stop sequences, EOS and length, and append one ``StepOutput`` per
        committed token to ``outputs`` and each finished request to
        ``finished``."""
        now = time.perf_counter()
        if dec_vals is not None:
            for i in active:
                r = at_dispatch[i]
                if spec_drafts is not None:
                    from repro.serve.spec import accept_tokens
                    d = spec_drafts.get(i, [])
                    committed = accept_tokens(
                        d, [int(t) for t in dec_vals[i][:len(d) + 1]])
                    self.spec_accepted += len(committed) - 1
                    # roll back rejected-draft blocks and advance the
                    # slot's frontier — but never for a slot the engine
                    # already recycled this iteration (a length-finishing
                    # pre-release or a preemption victim): its rid left
                    # the allocator, and its tokens commit below anyway
                    if r.state != RequestState.PREEMPTED \
                            and i not in pre_released:
                        self.backend.commit(i, r, len(committed))
                else:
                    committed = [int(dec_vals[i])]
                before = len(r.output)
                r.output.extend(committed)
                reason = r.check_finish(new_tokens=len(committed))
                if reason:
                    # a victim preempted this very iteration may finish on
                    # tokens it decoded before eviction: it holds no
                    # slot/blocks anymore — just pull it off the queue
                    if r.state == RequestState.PREEMPTED:
                        if r in self.queue:
                            self.queue.remove(r)
                        self._finish(r, None, reason, now, True, finished)
                    else:
                        self._finish(r, i, reason, now, i in pre_released,
                                     finished)
                # one StepOutput per surviving committed token (a finish
                # scan may have truncated accepted tokens behind a match);
                # only the last carries the finish reason
                tail = r.output[before:]
                for j, t in enumerate(tail):
                    last = j == len(tail) - 1
                    outputs.append(StepOutput(
                        rid=r.rid, token=t, state=r.state,
                        finish_reason=(r.finish_reason
                                       if reason and last else None),
                        qos=r.qos))
        for (req, slot, _), tok in zip(admitted, adm_vals):
            req.output.append(int(tok))
            if req.first_token_at is None:
                req.first_token_at = now
            req.state = RequestState.RUNNING
            reason = req.check_finish()
            if reason:
                # finished at its admission prefill: recycle before the
                # slot is vacated
                self._finish(req, slot, reason, now, False, finished)
            outputs.append(StepOutput(
                rid=req.rid, token=req.output[-1], state=req.state,
                finish_reason=req.finish_reason if reason else None,
                qos=req.qos))

    # -- observability -----------------------------------------------------

    def metrics(self) -> Dict[str, float]:
        """Engine-level serving counters (this instance method shadows the
        backend's attributes via ``__getattr__`` precedence — the
        module-level :func:`metrics` aggregates *finished requests*
        instead). Always includes the dispatch/transfer contract counters;
        on a prefix-caching paged backend it adds the cache economics:
        cached-block hit rate and the prefill tokens skipped via cached
        prefixes."""
        b = self.backend
        out: Dict[str, float] = {
            "iterations": float(self.iterations),
            "decode_dispatches": float(b.decode_dispatches),
            "transfers": float(b.transfers),
            "max_concurrent": float(self.max_concurrent),
        }
        # decode-iteration wall statistics (0.0 on a fresh engine — never
        # divide by an empty window) + chunked-prefill progress: jitter =
        # p99 − p50 iteration wall, the spread chunking exists to bound
        walls = np.asarray(self._iter_walls, np.float64)
        p50 = float(np.percentile(walls, 50)) if walls.size else 0.0
        p99 = float(np.percentile(walls, 99)) if walls.size else 0.0
        # per-committed-token normalized walls: a speculative iteration
        # commits several tokens, so the raw iteration wall overstates
        # its per-token latency — normalize by that iteration's commits
        # (idle iterations commit 0 and divide by 1). Both windows hold
        # the same last ITER_WINDOW iterations: _step appends to them
        # together, so they pair entry by entry.
        toks = np.asarray(self._iter_tokens, np.float64)
        per_tok = walls / np.maximum(toks, 1.0)
        tp50 = float(np.percentile(per_tok, 50)) if per_tok.size else 0.0
        tp99 = float(np.percentile(per_tok, 99)) if per_tok.size else 0.0
        drafted = float(self.spec_drafted)
        out.update({
            "iter_wall_p50_ms": p50 * 1e3,
            "iter_wall_p99_ms": p99 * 1e3,
            "decode_iter_jitter_ms": (p99 - p50) * 1e3,
            "iter_wall_per_token_p50_ms": tp50 * 1e3,
            "iter_wall_per_token_p99_ms": tp99 * 1e3,
            "spec_drafted": drafted,
            "spec_accepted": float(self.spec_accepted),
            "spec_accept_rate": (self.spec_accepted / drafted
                                 if drafted else 0.0),
            "prefill_chunks_in_flight": float(sum(
                1 for r in self.slots
                if r is not None and r.state == RequestState.PREFILL)),
            "prefill_chunks_dispatched": float(
                getattr(b, "prefill_chunk_dispatches", 0)),
            "prefill_chunk_stalls": float(self._chunk_stalls),
        })
        if getattr(b, "mesh", None) is not None:
            # mesh-sharded paged serving: aggregate + per-device pool
            # residency (the per-device numbers are what a fixed HBM
            # budget per chip actually constrains)
            out["mesh_devices"] = float(b.ndev)
            out["pool_bytes_total"] = float(b.pool_bytes)
            for d, nbytes in sorted(b.pool_bytes_by_device().items()):
                out[f"pool_bytes_dev{d}"] = float(nbytes)
            out["pool_blocks_total"] = float(b.layout.usable_blocks if
                                             b.kv_mode != "blocks" else
                                             b._dev_layout.usable_blocks
                                             * b.ndev)
            for d, nb in sorted(b.blocks_by_device().items()):
                out[f"pool_blocks_dev{d}"] = float(nb)
        # prefix-cache economics: one global allocator, or (block-sharded
        # mesh serving) summed over the per-device allocators
        allocs = getattr(b, "allocs", None)
        if allocs is None:
            alloc = getattr(b, "alloc", None)
            allocs = [alloc] if alloc is not None else []
        if not allocs or not getattr(b, "prefix_caching", False):
            return out
        hit = sum(a.hit_blocks for a in allocs)
        miss = sum(a.miss_blocks for a in allocs)
        looked = hit + miss
        total = b.prefill_tokens_total
        out.update({
            "prefix_cache_hit_blocks": float(hit),
            "prefix_cache_miss_blocks": float(miss),
            "prefix_cache_hit_rate": (hit / looked if looked else 0.0),
            "prefix_cache_evictions": float(
                sum(a.evictions for a in allocs)),
            "prefix_cache_cow_copies": float(
                sum(a.cow_copies for a in allocs)),
            "prefix_cached_blocks": float(
                sum(a.cached_blocks for a in allocs)),
            "prefill_tokens_total": float(total),
            "prefill_tokens_skipped": float(b.prefill_tokens_skipped),
            "prefill_skip_rate": (b.prefill_tokens_skipped / total
                                  if total else 0.0),
        })
        return out

    # -- drivers -----------------------------------------------------------

    def run_until_drained(self, max_iters: int = 10_000) -> List[Request]:
        done: List[Request] = []
        for _ in range(max_iters):
            _, finished = self._step()
            done.extend(finished)
            if self.idle:
                break
        return done

    def stream(self, handle: Union[Handle, Request]) -> Iterator[StepOutput]:
        """Step the engine and yield ``handle``'s tokens as they land.
        Terminates after the final token (its ``finish_reason`` set), or
        with a token-less terminal StepOutput if the request was aborted
        between tokens. Other requests keep being served by the same
        ``step()`` calls — interleave multiple ``stream()`` generators
        freely."""
        req = self.request(handle)
        cursor = 0
        reason_delivered = False
        while True:
            while cursor < len(req.output):
                cursor += 1
                final = req.finished and cursor == len(req.output)
                if final:
                    reason_delivered = True
                yield StepOutput(
                    rid=req.rid, token=req.output[cursor - 1],
                    state=req.state,
                    finish_reason=req.finish_reason if final else None,
                    qos=req.qos)
            if req.finished:
                if not reason_delivered:
                    yield StepOutput(rid=req.rid, token=None,
                                     state=req.state,
                                     finish_reason=req.finish_reason,
                                     qos=req.qos)
                return
            if self.idle:
                return
            self.step()


def metrics(done: List[Request]) -> Dict[str, float]:
    finished = [r for r in done if r.done_at is not None]
    if not finished:
        return {"requests": 0, "ttft_avg_s": 0.0, "latency_avg_s": 0.0,
                "tokens_per_s": 0.0}
    ttft = [r.first_token_at - r.submitted_at
            for r in finished if r.first_token_at is not None]
    lat = [r.done_at - r.submitted_at for r in finished]
    toks = sum(len(r.output) for r in finished)
    wall = (max(r.done_at for r in finished)
            - min(r.submitted_at for r in finished))
    return {
        "requests": len(finished),
        "ttft_avg_s": float(np.mean(ttft)) if ttft else 0.0,
        "latency_avg_s": float(np.mean(lat)) if lat else 0.0,
        "tokens_per_s": toks / wall if wall > 0 else 0.0,
    }
