"""Engine configuration for the serve layer.

``EngineConfig`` is the single construction surface of
:class:`repro.serve.api.LLMEngine`: it names the execution backend
(``backend``), the admission policy (``scheduler``), and every capacity /
sampling knob the backends share. The legacy engine classes in
``repro.serve.engine`` are shims that pin ``backend`` and keep the rest.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

# execution backends (repro.serve.backends) and their legacy aliases
BACKENDS = ("slot", "arena", "paged")
_BACKEND_ALIASES = {
    "reference": "slot",     # sequential per-slot baseline (ServeEngine)
    "batched": "arena",      # dense [slots, max_len] arena (BatchedServeEngine)
    "dense": "arena",
}

# admission schedulers (repro.serve.scheduler)
SCHEDULERS = ("fcfs", "bounded", "qos")

# speculative-decoding draft methods (re-exported from repro.serve.spec)
from repro.serve.spec import SPEC_METHODS  # noqa: E402


def canonical_backend(name: str) -> str:
    name = _BACKEND_ALIASES.get(name, name)
    if name not in BACKENDS:
        raise ValueError(
            f"unknown serve backend {name!r} "
            f"(supported: {', '.join(BACKENDS)}; legacy aliases: "
            f"{', '.join(sorted(_BACKEND_ALIASES))})")
    return name


@dataclasses.dataclass
class EngineConfig:
    slots: int = 4               # decode batch size
    max_len: int = 256
    admit_window: int = 8        # bounded-priority window (see scheduler.py)
    admit_batch: int = 1         # max admissions per iteration (cold-start
    #                              ramp: `slots` concurrency is reached in
    #                              ceil(slots/admit_batch) iterations)
    greedy: bool = True
    temperature: float = 1.0     # used when greedy=False
    seed: int = 0                # sampling PRNG seed (vectorized backends)
    prefill_buckets: bool = True  # pad admission prompts to pow2 buckets
    min_bucket: int = 8
    # paged backend: KV block size and pool size. With num_blocks=None the
    # pool matches the dense arena's token budget (slots · max_len) — same
    # memory, strictly more admissible requests.
    block_len: int = 16
    num_blocks: Optional[int] = None
    # paged attention backend (None → from the platform: the Pallas kernel
    # on a TPU, the XLA oracle elsewhere). Validated at engine
    # construction: quantized archs must name a backend that implements
    # int8 block pools.
    attn_backend: Optional[str] = None
    # paged backend: content-addressed prefix caching. Completed KV blocks
    # are published under a chained hash of their token prefix; a new
    # request whose prompt shares a published full-block prefix maps those
    # blocks into its table (refcounted, copy-on-write) and prefills only
    # the uncached suffix. Released blocks park in an LRU and are reused
    # or evicted on demand. Off by default: with caching on, a drained
    # engine intentionally retains cached blocks (free + cached == usable)
    # instead of returning everything to the free list. Ring (sliding-
    # window) layouts opt out automatically.
    prefix_cache: bool = False
    # paged backend: chunked prefill co-scheduled with decode. When set,
    # an admission's prefill is split into block-aligned chunks of at most
    # this many tokens per engine iteration (the budget is shared across
    # every in-flight prefill), so one iteration's dispatch work is
    # bounded: ≤ budget of prefill chunk work + one batched decode + one
    # fetch. Must be a multiple of block_len (chunk boundaries land on
    # block boundaries, keeping the suffix-resume reduction order
    # unchanged — chunked output is token-identical to monolithic) and
    # >= block_len. None (the default) keeps monolithic admission
    # prefills. Ring (sliding-window) layouts opt out automatically: a
    # ring arena cannot resume mid-history.
    prefill_chunk_tokens: Optional[int] = None
    # paged backend: speculative decoding. When spec_tokens = k > 0, a
    # host-side drafter proposes up to k tokens per running slot each
    # iteration and a single small-q verify dispatch scores all k + 1
    # positions; greedy acceptance commits the longest agreeing prefix
    # (plus the bonus token) and rolls the rest back at block granularity.
    # Greedy acceptance keeps the engine token-identical to spec_tokens=0.
    # Requires the paged backend; ring (sliding-window) layouts and
    # mesh-sharded pools opt out automatically (like chunked prefill).
    spec_tokens: int = 0
    # draft method: "ngram" — prompt-lookup n-gram matching over the
    # request's own prompt + generated tokens (no second model)
    spec_method: str = "ngram"
    # paged backend on a mesh: the mesh axis names LLMEngine accepts, and
    # how the block pool is sharded over the "model" axis. mesh_axes[0]
    # must be "model" (the serve_rules TP axis); extra axes must have
    # extent 1 on the mesh actually passed to the engine. kv_shard:
    #   "auto"   — head-sharded when n_kv_heads divides the mesh, else
    #              block-sharded (slots pinned to the device owning their
    #              blocks);
    #   "heads"  — force head sharding (raises if it doesn't divide);
    #   "blocks" — force block sharding.
    # Ignored unless a mesh is passed to LLMEngine.
    mesh_axes: tuple = ("model",)
    kv_shard: str = "auto"
    # -- the LLMEngine construction surface --------------------------------
    # execution backend: "slot" (sequential per-slot reference), "arena"
    # (dense batched arena, the default), "paged" (shared block pool)
    backend: str = "arena"
    # admission policy: "fcfs" (arrival order, never preempts), "bounded"
    # (the legacy bounded-priority forced-admission path, the default),
    # "qos" (two traffic classes: "rt" gets a bounded admission window,
    # "be" fills the remaining slots — the memory island's arbiter twin)
    scheduler: str = "bounded"
    # qos scheduler: max iterations an "rt" lane head may wait before a
    # forced (preempting) admission — the software twin of the island
    # arbiter's bounded narrow-priority window
    rt_window: int = 2
    # qos scheduler: after this many consecutive rt admissions while a
    # "be" request waits, the next free-slot admission is granted to "be"
    # (the arbiter's guaranteed wide beat — rt priority is bounded, so
    # best-effort traffic is never starved of *grants*; it is never
    # preempted by this path)
    be_grant_window: int = 8
    # qos scheduler: optional direct bound on the best-effort share of
    # decode tokens. When set (0 < share < 1), the scheduler withholds
    # "be" admissions while the running be-token fraction exceeds the
    # share (rt demand permitting) — token-rate shaping on top of the
    # grant-count fairness above. None disables shaping.
    be_token_share: Optional[float] = None
    # how many *finished* (done/aborted) requests the engine keeps
    # addressable by handle after completion. None keeps all — right for
    # batch jobs that read results after run_until_drained(); a
    # long-running server loop should set a bound, or the per-request
    # registry grows without limit. Oldest-finished are dropped first;
    # a dropped handle raises KeyError from request()/stream()/abort().
    retain_finished: Optional[int] = None

    def effective_temperature(self, temperature: Optional[float]) -> float:
        """Resolve a request's decode temperature against the engine
        defaults: the request's own when set, else 0 (greedy) under
        ``greedy=True``, else the engine ``temperature``. The single
        definition both the sampling vectors and the slot backend's
        greedy-only gate resolve through."""
        if temperature is not None:
            return float(temperature)
        return 0.0 if self.greedy else float(self.temperature)

    def __post_init__(self):
        self.backend = canonical_backend(self.backend)
        if self.scheduler not in SCHEDULERS:
            raise ValueError(
                f"unknown scheduler {self.scheduler!r} "
                f"(supported: {', '.join(SCHEDULERS)})")
        if self.admit_batch < 1:
            raise ValueError(
                f"admit_batch must be >= 1, got {self.admit_batch} "
                f"(0 would starve admission and break the bounded-priority "
                f"forced path)")
        if self.rt_window < 1:
            raise ValueError(f"rt_window must be >= 1, got {self.rt_window}")
        if self.be_grant_window < 1:
            raise ValueError(
                f"be_grant_window must be >= 1, got {self.be_grant_window} "
                f"(0 would promote the be lane every iteration, inverting "
                f"rt priority)")
        self.mesh_axes = tuple(self.mesh_axes)
        if not self.mesh_axes or self.mesh_axes[0] != "model":
            raise ValueError(
                f"mesh_axes must start with 'model' (the serve_rules TP "
                f"axis), got {self.mesh_axes!r}")
        if self.kv_shard not in ("auto", "heads", "blocks"):
            raise ValueError(
                f"kv_shard must be auto|heads|blocks, got {self.kv_shard!r}")
        if self.prefill_chunk_tokens is not None:
            c = self.prefill_chunk_tokens
            if c < self.block_len or c % self.block_len:
                raise ValueError(
                    f"prefill_chunk_tokens must be a multiple of block_len "
                    f"({self.block_len}) and >= it, got {c} — chunk "
                    f"boundaries must land on block boundaries so each "
                    f"chunk writes whole pool blocks and the suffix-resume "
                    f"reduction order is unchanged")
        if self.spec_tokens < 0:
            raise ValueError(
                f"spec_tokens must be >= 0, got {self.spec_tokens}")
        if self.spec_method not in SPEC_METHODS:
            raise ValueError(
                f"unknown spec_method {self.spec_method!r} "
                f"(supported: {', '.join(SPEC_METHODS)})")
        if self.be_token_share is not None and not (
                0.0 < self.be_token_share < 1.0):
            raise ValueError(
                f"be_token_share must be in (0, 1) when set, got "
                f"{self.be_token_share} (0 starves be admission outright; "
                f"1 disables shaping — use None for that)")
        # NOTE: attn_backend × backend compatibility is validated by
        # LLMEngine, not here — the legacy shims pin `backend` *after*
        # config construction (dataclasses.replace), so a config carrying
        # attn_backend may legitimately exist before the backend is final.
