"""Serve-engine benchmark: per-slot baseline vs batched vs paged engines.

Runs the same mixed prompt-length workload through the sequential per-slot
reference engine, the vectorized ``BatchedServeEngine`` (dense
``[slots, max_len]`` KV arena) and the ``PagedServeEngine`` (shared
block-pool KV with a per-slot block table), and reports tokens/s, TTFT,
p50/p99 per-iteration latency, and the dispatch / transfer / retrace
counters that make the QoS dataflow contract measurable.

Claims validated:
  * ≥ 3x tokens/s for the batched engine over the per-slot baseline
    (ISSUE 1) — the paged engine keeps the same contract;
  * exactly one decode dispatch and one device→host fetch per iteration
    for both vectorized engines;
  * **capacity**: at the dense arena's exact KV token budget, the paged
    pool admits ≥ 2x the concurrent requests on a short-request workload
    (ISSUE 2) — the block pool recycles what short requests never use;
  * **sliding-window capacity** (ISSUE 3 ring blocks): a model whose
    ``local_window < max_len`` serves on the paged engine token-identical
    to the dense arena while every sliding-window layer's pool holds only
    ``slots · (ceil(window/block)+1)`` blocks — per-sliding-layer KV
    residency bounded by the window, not ``max_len``;
  * **int8 block capacity** (ISSUE 4): a quantized arch stores K/V
    natively as int8 blocks + per-block scales, roughly halving pool
    bytes per resident token vs the old float-block layout — so at the
    *same pool byte budget* the int8 pool admits ≥ 1.8x the concurrent
    requests, token-identical to the dense int8 reference throughout.

  * **prefix caching** (ISSUE 6): on a workload where ≥ 50% of requests
    share a 256-token system prompt, content-addressed block reuse
    (refcounted, copy-on-write, LRU) prefills only the uncached suffix —
    mean TTFT drops ≥ 1.5x while outputs stay identical to the
    non-caching engine up to certified float near-ties (the
    suffix-resume attention sums in a different order than the wide full
    prefill, so an argmax may flip only where the reference top-2 logits
    are within rounding distance);

  * **chunked prefill** (ISSUE 8): with long-prompt best-effort
    admissions landing next to live rt decodes, splitting each prefill
    into block-aligned chunks co-scheduled with decode bounds every
    iteration's dispatch work — p99 decode-iteration jitter (p99 − p50
    iteration wall) drops ≥ 4x vs monolithic admission at ≥ 0.9x the
    aggregate tokens/s, token-identically;

  * **mesh scaling** (ISSUE 7 shard_map serving; ``--mesh-scaling``, a
    run of its own in the process that holds the devices): at a fixed
    per-device block budget, the mesh-sharded pool's aggregate capacity
    scales with device count — ≥ 1.8x the concurrent requests at 2
    devices and monotone to 8 — while outputs stay token-identical to
    the single-device engine (heads mode slices the KV-head axis, blocks
    mode partitions the pool; the sweep crosses both);

  * **QoS traffic classes** (ISSUE 5 scheduler/engine split): with every
    slot saturated by best-effort (``"be"``) traffic, the two-class QoS
    scheduler holds latency-critical (``"rt"``) p99 TTFT ≥ 4x below FCFS
    at equal aggregate tokens/s (within 10%) — the serving-layer twin of
    the island arbiter's 16x narrow-latency reduction (Fig. 6b);

  * **speculative decoding** (ISSUE 9): on a repetitive-text workload
    (periodic prompts, greedy continuations that settle into short
    cycles — the boilerplate/code-completion case prompt-lookup drafting
    targets), n-gram drafts verified in one small-q dispatch commit
    several tokens per iteration: ≥ 1.3x tokens/s per slot over the
    plain paged engine, with outputs asserted token-identical.

Emits ``BENCH_serve.json`` with the batched/paged throughputs, the
paged-vs-dense concurrency comparison, the sliding-window (ring-block)
capacity entry, the ``paged.int8_blocks`` entry (bytes/token, capacity
ratio, tokens/s), the ``paged.prefix_cache`` entry (TTFT reduction, hit
rate, prefill tokens skipped), the ``paged.speculative`` entry
(tokens/s ratio, accept rate, iteration reduction) and the
``qos_classes`` rt-vs-be TTFT contrast so future PRs can track all of
them.

The three engine runs drive the deprecated shim classes on purpose — they
are thin wrappers over ``repro.serve.LLMEngine`` and this keeps the
legacy surface exercised; the QoS run constructs ``LLMEngine`` directly.
"""

from __future__ import annotations

import json
import time

import numpy as np

SLOTS = 8
REQUESTS = 32
MAX_NEW = 24
MAX_LEN = 64
BLOCK_LEN = 8
CAP_REQUESTS = 48


def _workload(cfg, seed=0):
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    return [
        Request(rid=rid,
                prompt=rng.integers(0, cfg.vocab,
                                    size=int(rng.integers(3, 28))
                                    ).astype(np.int32),
                max_new_tokens=MAX_NEW)
        for rid in range(REQUESTS)
    ]


def _short_workload(cfg, seed=1, n=CAP_REQUESTS):
    """Short requests: worst-case extent ≤ 32 tokens (4 blocks of 8), so a
    512-token budget holds 16 of them at once vs 8 dense slots."""
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    return [
        Request(rid=rid,
                prompt=rng.integers(0, cfg.vocab,
                                    size=int(rng.integers(3, 9))
                                    ).astype(np.int32),
                max_new_tokens=MAX_NEW)
        for rid in range(n)
    ]


def _drive(engine, requests):
    """Run to drain, timing every engine iteration; returns (done, stats)."""
    for r in requests:
        engine.submit(r)
    done, iter_s = [], []
    t0 = time.perf_counter()
    for _ in range(10_000):  # bounded like run_until_drained
        if engine.idle:
            break
        it0 = time.perf_counter()
        done.extend(engine.step())
        iter_s.append(time.perf_counter() - it0)
    assert engine.idle, "engine failed to drain within 10k iterations"
    wall = time.perf_counter() - t0
    return done, wall, np.asarray(iter_s)


PFX_SLOTS = 4
PFX_REQUESTS = 24
PFX_SHARED = 18          # 75% of the workload shares the system prompt
PFX_SYS_BLOCKS = 32      # 256-token shared prefix at block_len 8
PFX_MAX_LEN = 320        # prompt (≤267) + decode under the pool cap
PFX_NEW = 1              # TTFT gate: the first token comes out of the
#                          prefill dispatch itself, so decode iterations
#                          (identical cost in both settings — the decode
#                          path is pinned token-identical by the test
#                          matrix) would only dilute the contrast


def _prefix_workload(cfg):
    """Deterministic shared-system-prompt workload: (sys_prompt, prompts).
    First ``PFX_SHARED`` prompts are sys_prompt + a random 3..11-token
    tail, the rest are unshared short prompts."""
    rng = np.random.default_rng(6)
    sys_prompt = rng.integers(
        0, cfg.vocab, size=PFX_SYS_BLOCKS * BLOCK_LEN).astype(np.int32)
    prompts = {}
    for rid in range(PFX_REQUESTS):
        tail = rng.integers(0, cfg.vocab,
                            size=int(rng.integers(3, 12))).astype(np.int32)
        prompts[rid] = (np.concatenate([sys_prompt, tail])
                        if rid < PFX_SHARED else tail)
    return sys_prompt, prompts


def _prefix_cache_run(arch, params, cfg, prompts, sys_prompt, enabled):
    """One warmed run of the shared-system-prompt workload with prefix
    caching on or off; returns (outputs, mean TTFT, engine)."""
    from repro.serve import EngineConfig, LLMEngine

    ec = EngineConfig(slots=PFX_SLOTS, max_len=PFX_MAX_LEN,
                      block_len=BLOCK_LEN, backend="paged",
                      prefix_cache=enabled, admit_batch=2)
    eng = LLMEngine(arch, params, ec)
    # warm every prefill trace the timed phase can hit — shared prompts
    # pad to width 264 or 272 (block-rounded decode extent) when cold,
    # and to suffix width 8 / 16 over a 32-block hit when cached;
    # unshared prompts bucket to 8 / 16 — so the timed section
    # measures serving, not tracing. On the caching engine the first warm
    # request also publishes the system prompt, which is exactly the
    # steady state the claim is about. (The retrace assert below keeps
    # this warm set honest if the workload shape ever changes.)
    for i, tail_n in enumerate((4, 8, 5)):
        eng.add_request(
            np.concatenate([sys_prompt,
                            np.arange(tail_n, dtype=np.int32)]),
            max_new_tokens=2, rid=10_000 + i)
    # plain warms must not share full blocks with each other (arange
    # prefixes would: the 8-token warm's block is a prefix of the 9-token
    # one, turning the second into an unintended cache hit with a suffix
    # trace instead of the plain bucket-16 trace the timed phase needs)
    wrng = np.random.default_rng(7)
    for i, n in enumerate((4, 8, 9)):
        eng.add_request(wrng.integers(0, cfg.vocab, size=n).astype(np.int32),
                        max_new_tokens=2, rid=10_010 + i)
    eng.run_until_drained()
    traces_after_warm = eng.prefill_traces

    for rid in range(PFX_REQUESTS):
        eng.add_request(prompts[rid], max_new_tokens=PFX_NEW, rid=rid)
    eng.run_until_drained()
    assert eng.prefill_traces == traces_after_warm, (
        "timed phase retraced a prefill shape the warm set missed: "
        f"{traces_after_warm} -> {eng.prefill_traces}")
    reqs = [eng.request(r) for r in range(PFX_REQUESTS)]
    assert all(len(r.output) == PFX_NEW for r in reqs)
    ttft = np.asarray([r.first_token_at - r.submitted_at for r in reqs])
    return {r.rid: list(r.output) for r in reqs}, float(ttft.mean()), eng


def _certify_near_tie(arch, params, prompt, out_off, out_on, tol=2e-2):
    """Certify a cache-on/off divergence as a floating-point near-tie.

    The suffix-resume prefill attends over (gathered prefix K/V + small
    suffix bucket) where the full prefill runs one wide masked attention —
    same math, different reduction order, so argmax can flip when the
    top-2 logits are within rounding distance. At the *first* differing
    position (everything after it legitimately diverges via feedback),
    both chosen tokens must sit within ``tol`` of each other and of the
    reference top logit, computed by the plain (non-paged) forward."""
    import jax.numpy as jnp

    k = next(i for i in range(min(len(out_off), len(out_on)))
             if out_off[i] != out_on[i])
    ids = np.concatenate([prompt, out_off[:k]]).astype(np.int32)
    logits = np.asarray(
        arch.forward(params, jnp.asarray(ids)[None])[0, -1], np.float64)
    a, b = logits[out_off[k]], logits[out_on[k]]
    top = float(logits.max())
    assert abs(a - b) <= tol and top - min(a, b) <= tol, (
        f"cache-on/off divergence is NOT a near-tie: first flip at +{k}, "
        f"off tok logit {a:.6f}, on tok logit {b:.6f}, top {top:.6f}")
    return k


def _prefix_cache_contrast(arch, params, cfg):
    """Cache-on vs cache-off on the shared-prefix workload.

    Token contract: outputs are identical except for certified
    floating-point near-ties — any request whose greedy tokens differ
    must flip at a position where the reference top-2 logits are within
    rounding distance (the suffix-resume prefill sums attention in a
    different order than the wide full prefill). Mean TTFT uses the best
    of three timed runs per setting (tokens are deterministic; wall clock
    is not)."""
    sys_prompt, prompts = _prefix_workload(cfg)
    outs, ttfts, engs = {}, {}, {}
    for enabled in (False, True):
        trials = [_prefix_cache_run(arch, params, cfg, prompts, sys_prompt,
                                    enabled) for _ in range(3)]
        assert all(t[0] == trials[0][0] for t in trials)
        outs[enabled] = trials[0][0]
        ttfts[enabled] = min(t[1] for t in trials)
        engs[enabled] = trials[0][2]
    flips = []
    for rid in range(PFX_REQUESTS):
        if outs[True][rid] != outs[False][rid]:
            k = _certify_near_tie(arch, params, prompts[rid],
                                  outs[False][rid], outs[True][rid])
            flips.append({"rid": rid, "position": k})
    assert len(flips) <= PFX_REQUESTS // 4, (
        f"too many near-tie flips ({len(flips)}/{PFX_REQUESTS}) — "
        "that is a numerics bug, not rounding noise")
    m = engs[True].metrics()
    m_off = engs[False].metrics()
    assert "prefix_cache_hit_blocks" not in m_off
    return {
        "arch": cfg.name,
        "block_len": BLOCK_LEN,
        "requests": PFX_REQUESTS,
        "shared_fraction": PFX_SHARED / PFX_REQUESTS,
        "shared_prefix_tokens": PFX_SYS_BLOCKS * BLOCK_LEN,
        "ttft_avg_ms_off": ttfts[False] * 1e3,
        "ttft_avg_ms_on": ttfts[True] * 1e3,
        "ttft_reduction": ttfts[False] / ttfts[True],
        "hit_rate": m["prefix_cache_hit_rate"],
        "prefill_tokens_skipped": m["prefill_tokens_skipped"],
        "prefill_skip_rate": m["prefill_skip_rate"],
        "evictions": m["prefix_cache_evictions"],
        "near_tie_flips": len(flips),
        "token_identity": "exact or certified near-tie (float)",
    }


MESH_DEVICES = (1, 2, 4, 8)
MESH_BUDGET = 13       # per-device block budget (incl. the trash block)
MESH_SLOTS = 48
MESH_NEW = 8


def _mesh_scaling(devices):
    """Mesh capacity sweep at a fixed per-device block budget, in this
    process, over the first ``n`` of its devices for each ``n`` in
    ``devices``.

    Every mesh serves the same short-request workload with
    ``MESH_BUDGET`` blocks of per-device pool memory; reports peak
    concurrency and warm tokens/s per mesh. Outputs are asserted
    token-identical across device counts."""
    import jax

    from repro import configs
    from repro.launch.mesh import make_serve_mesh
    from repro.models import registry, schema as schema_lib
    from repro.serve import EngineConfig, LLMEngine

    cfg = configs.smoke_config("phi3-mini-3.8b")
    arch = registry.build(cfg)
    params = schema_lib.init_params(arch.schema(), jax.random.key(0))

    def workload():
        # short requests: 4..8-token prompts + MESH_NEW decoded tokens
        # stay inside 2 blocks each, so capacity = usable_blocks // 2 per
        # device
        rng = np.random.default_rng(11)
        return [rng.integers(0, cfg.vocab, size=int(rng.integers(4, 9))
                             ).astype(np.int32) for _ in range(MESH_SLOTS)]

    entries, base = [], None
    for n in devices:
        mesh = make_serve_mesh(n)
        # num_blocks = MESH_BUDGET * n holds per-device bytes fixed in BOTH
        # modes: heads mode stores all blocks but a 1/n head-slice of each;
        # blocks mode stores full-head blocks but only 1/n of them
        ec = EngineConfig(slots=MESH_SLOTS, max_len=64, block_len=BLOCK_LEN,
                          backend="paged", num_blocks=MESH_BUDGET * n,
                          admit_batch=MESH_SLOTS)
        eng = LLMEngine(arch, params, ec, mesh=mesh)
        for rid, p in enumerate(workload()):
            eng.add_request(p, max_new_tokens=MESH_NEW, rid=rid)
        out = {r.rid: list(r.output) for r in eng.run_until_drained()}
        if base is None:
            base = out
        assert out == base, f"mesh={n} diverged from single-device output"
        # timed second drain: every trace is warm, so this measures serving
        for rid, p in enumerate(workload()):
            eng.add_request(p, max_new_tokens=MESH_NEW, rid=10_000 + rid)
        t0 = time.perf_counter()
        eng.run_until_drained()
        wall = time.perf_counter() - t0
        m = eng.metrics()
        entries.append({
            "devices": n,
            "kv_shard": eng.kv_mode,
            "num_blocks": MESH_BUDGET * n,
            "pool_bytes_per_device": max(
                v for k, v in m.items() if k.startswith("pool_bytes_dev")),
            "pool_blocks_total": m["pool_blocks_total"],
            "concurrent": eng.max_concurrent,
            "tokens_per_s": MESH_SLOTS * MESH_NEW / wall,
        })
    cap = {e["devices"]: e["concurrent"] for e in entries}
    return {
        "arch": "phi3-mini-3.8b",
        "block_len": BLOCK_LEN,
        "budget_blocks_per_device": MESH_BUDGET,
        "slots": MESH_SLOTS,
        "device": jax.devices()[0].platform,
        "entries": entries,
        "capacity_ratio_2dev": cap[2] / cap[1],
        "capacity_ratio_max": cap[max(cap)] / cap[1],
        "token_identical_across_meshes": True,
    }


def mesh_main(csv: bool = True):
    """``--mesh-scaling``: the mesh sweep alone, over the devices this
    process holds (on the CPU, start it under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``). Writes its
    entry into ``BENCH_serve.json`` under ``paged.mesh_scaling``."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = tuple(n for n in MESH_DEVICES if n <= len(jax.devices()))
    if 2 not in devices:
        raise SystemExit(
            f"--mesh-scaling needs at least 2 devices, this process has "
            f"{len(jax.devices())}; on the CPU run it under XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8")
    mesh_scaling = _mesh_scaling(devices)
    caps = {e["devices"]: e["concurrent"] for e in mesh_scaling["entries"]}
    row = ("serve_paged_mesh_scaling", 0.0,
           "concurrent=" + "/".join(f"{caps[n]}@{n}dev" for n in devices)
           + f"|2dev_ratio={mesh_scaling['capacity_ratio_2dev']:.2f}x "
           f"(claim: >=1.8x)|"
           f"{max(devices)}dev_ratio="
           f"{mesh_scaling['capacity_ratio_max']:.2f}x|"
           f"budget={MESH_BUDGET} blocks/device|identical=yes")
    if csv:
        print(f"{row[0]},{row[1]:.1f},{row[2]}")
    try:
        with open("BENCH_serve.json") as f:
            bench = json.load(f)
    except FileNotFoundError:
        bench = {}
    bench.setdefault("paged", {})["mesh_scaling"] = mesh_scaling
    with open("BENCH_serve.json", "w") as f:
        json.dump(bench, f, indent=2)
    assert mesh_scaling["capacity_ratio_2dev"] >= 1.8, (
        f"2-device mesh admitted only "
        f"{mesh_scaling['capacity_ratio_2dev']:.2f}x the single-device "
        f"concurrency at an equal per-device pool budget (claim: >=1.8x)")
    for lo, hi in zip(devices, devices[1:]):
        assert caps[hi] >= caps[lo], (
            f"mesh capacity not monotone: {caps[lo]} concurrent at "
            f"{lo} devices but {caps[hi]} at {hi}")
    assert caps[max(devices)] > caps[1], (
        f"mesh capacity flat from 1->{max(devices)} devices")
    return [row]


QOS_SLOTS = 4
QOS_BE_N = 32
QOS_BE_NEW = 48        # long be decodes amortize the qos run's extra
#                        prefill dispatches (rt admissions + preemption
#                        continuations) so aggregate tokens/s stays equal
QOS_RT_N = 4
QOS_RT_NEW = 6


def _qos_run(arch, params, cfg, sched):
    """One warmed, timed contention run under ``sched``: slots saturated
    by "be" traffic, "rt" requests arriving mid-flight."""
    from repro.serve import EngineConfig, LLMEngine

    ec = EngineConfig(slots=QOS_SLOTS, max_len=MAX_LEN,
                      scheduler=sched, rt_window=2, admit_batch=4)
    eng = LLMEngine(arch, params, ec)
    # warm the jit caches (decode + every pow2 prefill bucket the
    # workload and its preemption continuations can hit) so the timed
    # section measures steady-state serving, not compilation
    for i, n in enumerate((5, 12, 28, 44)):
        eng.add_request(np.arange(n, dtype=np.int32) % cfg.vocab,
                        max_new_tokens=2, rid=10_000 + i)
    eng.run_until_drained()

    rng = np.random.default_rng(4)
    # rt arrivals land early, while be continuations are still short —
    # preemption re-prefill cost scales with continuation length, and the
    # equal-throughput claim is about scheduling, not about re-prefilling
    # near-max_len histories
    rt_at = {6 + 6 * k: k for k in range(QOS_RT_N)}    # iteration -> rid
    for rid in range(QOS_BE_N):
        eng.add_request(
            rng.integers(0, cfg.vocab,
                         size=int(rng.integers(8, 13))).astype(np.int32),
            max_new_tokens=QOS_BE_NEW, qos="be", rid=rid)
    submitted_rt = 0
    iter_s = []
    for it in range(10_000):
        if eng.idle and submitted_rt == QOS_RT_N:
            break
        if it in rt_at:
            eng.add_request(
                rng.integers(0, cfg.vocab, size=5).astype(np.int32),
                max_new_tokens=QOS_RT_NEW, qos="rt", rid=100 + rt_at[it])
            submitted_rt += 1
        it0 = time.perf_counter()
        eng.step()
        iter_s.append(time.perf_counter() - it0)
    # stall-robust wall clock: clip iteration times at 50x the run median
    # (a prefill-heavy iteration is ~10-20x a decode step, far below the
    # clip; a page-cache or GC stall is far above it). The run median is
    # the cost of one batched decode dispatch — a fixed-shape jitted call,
    # identical for both schedulers — so wall/median is the run's work in
    # decode-iteration equivalents, a machine-speed-free measure the
    # scheduler comparison can use without minutes-apart drift noise.
    iter_s = np.asarray(iter_s)
    med = float(np.median(iter_s))
    wall = float(np.minimum(iter_s, 50 * med).sum())
    work_units = wall / med
    assert eng.idle, f"{sched} run failed to drain"
    reqs = [eng.request(r) for r in range(QOS_BE_N)] + \
           [eng.request(100 + k) for k in range(QOS_RT_N)]
    assert all(len(r.output) == (QOS_BE_NEW if r.qos == "be"
                                 else QOS_RT_NEW) for r in reqs)
    ttft = {q: [r.first_token_at - r.submitted_at
                for r in reqs if r.qos == q] for q in ("rt", "be")}
    return {
        "rt_p50_ms": float(np.percentile(ttft["rt"], 50) * 1e3),
        "rt_p99_ms": float(np.percentile(ttft["rt"], 99) * 1e3),
        "be_p50_ms": float(np.percentile(ttft["be"], 50) * 1e3),
        "be_p99_ms": float(np.percentile(ttft["be"], 99) * 1e3),
        "tokens_per_s": sum(len(r.output) for r in reqs) / wall,
        "tokens_per_work_unit": sum(len(r.output) for r in reqs)
        / work_units,
        "preemptions": sum(r.preemptions for r in reqs),
        "iterations": eng.iterations,
    }


def _qos_contention(arch, params, cfg):
    """Identical workload under the FCFS and QoS schedulers; per-class
    TTFT percentiles + aggregate throughput. Best-of-three timed runs per
    scheduler (tokens are deterministic; wall clock is not — one stalled
    run must not fake a throughput gap between schedulers), and the
    scheduler-vs-scheduler throughput ratio uses the speed-normalized
    ``tokens_per_work_unit`` so machine drift between the minutes-apart
    runs cancels (per-run token *rates* stay raw wall-clock)."""
    out = {}
    for sched in ("fcfs", "qos"):
        trials = [_qos_run(arch, params, cfg, sched) for _ in range(3)]
        out[sched] = max(trials, key=lambda t: t["tokens_per_work_unit"])
    return {
        "arch": cfg.name,
        "slots": QOS_SLOTS,
        "rt_window": 2,
        "be_requests": QOS_BE_N,
        "rt_requests": QOS_RT_N,
        "fcfs": out["fcfs"],
        "qos": out["qos"],
        "rt_p99_improvement": out["fcfs"]["rt_p99_ms"]
        / out["qos"]["rt_p99_ms"],
        "tokens_per_s_ratio": out["qos"]["tokens_per_work_unit"]
        / out["fcfs"]["tokens_per_work_unit"],
    }


# chunked prefill: long-prompt be admissions landing next to live rt
# decodes. Unchunked, every admission iteration pays a monolithic
# CHK_PROMPT-token prefill dispatch — a wall-clock spike every running
# decode waits out; chunked, the same work lands CHK_CHUNK tokens per
# iteration, so the p99 decode-iteration wall stays near the p50.
CHK_SLOTS = 4
CHK_PROMPT = 1280       # 160 blocks → 8 chunks of CHK_CHUNK; long enough
#                         that one monolithic dispatch dwarfs a decode
CHK_CHUNK = 160
CHK_MAX_LEN = 1344
CHK_BE_N = 8
CHK_BE_NEW = 4
CHK_RT_N = 2
CHK_RT_NEW = 90         # rt decodes span the whole run — the victims of
#                         unchunked admission spikes
CHK_BE_EVERY = 12       # be arrival spacing (iterations), staggered so
#                         ≤ 1 prefill is usually in flight


def _chunked_prefill_run(arch, params, cfg, chunk):
    """One warmed, timed adversarial run: CHK_RT_N rt requests decode
    throughout while CHK_BE_N long-prompt be requests arrive every
    CHK_BE_EVERY iterations. ``chunk=None`` is the monolithic baseline.
    Returns per-iteration wall percentiles + outputs (prompt lengths are
    fixed at CHK_PROMPT so both modes replay warmed traces only)."""
    from repro.serve import EngineConfig, LLMEngine

    ec = EngineConfig(slots=CHK_SLOTS, max_len=CHK_MAX_LEN,
                      block_len=BLOCK_LEN, backend="paged",
                      scheduler="qos", rt_window=2, admit_batch=1,
                      prefill_chunk_tokens=chunk)
    eng = LLMEngine(arch, params, ec)

    rng = np.random.default_rng(7)
    rt_prompts = [rng.integers(0, cfg.vocab, size=5).astype(np.int32)
                  for _ in range(CHK_RT_N)]
    be_prompts = [rng.integers(0, cfg.vocab,
                               size=CHK_PROMPT).astype(np.int32)
                  for _ in range(CHK_BE_N)]

    # warm every trace the timed run can hit on the SAME engine (the jit
    # caches are per-backend instance): the decode step, the short rt
    # admission bucket, and the long prompt's full chunk ladder (start=c
    # is static — one trace per resume depth) / the monolithic pad
    eng.add_request(rt_prompts[0], max_new_tokens=2, qos="rt", rid=900)
    eng.add_request(be_prompts[0], max_new_tokens=2, qos="be", rid=901)
    eng.run_until_drained()
    traces0 = eng.decode_traces + eng.prefill_traces
    be_at = {4 + CHK_BE_EVERY * k: k for k in range(CHK_BE_N)}
    for k in range(CHK_RT_N):
        eng.add_request(rt_prompts[k], max_new_tokens=CHK_RT_NEW,
                        qos="rt", rid=100 + k)
    submitted_be = 0
    iter_s = []
    for it in range(10_000):
        if eng.idle and submitted_be == CHK_BE_N:
            break
        if it in be_at:
            eng.add_request(be_prompts[be_at[it]],
                            max_new_tokens=CHK_BE_NEW, qos="be",
                            rid=be_at[it])
            submitted_be += 1
        it0 = time.perf_counter()
        eng.step()
        iter_s.append(time.perf_counter() - it0)
    assert eng.idle, "chunked-prefill run failed to drain"
    # the warm set was complete: the timed section replayed traces only
    # (a mid-run compile would fake a jitter spike in either mode)
    assert eng.decode_traces + eng.prefill_traces == traces0, (
        "chunked-prefill timed section retraced")
    reqs = [eng.request(r) for r in range(CHK_BE_N)] + \
           [eng.request(100 + k) for k in range(CHK_RT_N)]
    assert all(len(r.output) == (CHK_BE_NEW if r.qos == "be"
                                 else CHK_RT_NEW) for r in reqs)
    iter_s = np.asarray(iter_s)
    med = float(np.median(iter_s))
    p50 = float(np.percentile(iter_s, 50))
    p99 = float(np.percentile(iter_s, 99))
    # same stall-robust wall clock as the qos run: clip at 50x the run
    # median (well above a real prefill spike, well below an OS stall)
    wall = float(np.minimum(iter_s, 50 * med).sum())
    toks = sum(len(r.output) for r in reqs)
    return {
        "iter_wall_p50_ms": p50 * 1e3,
        "iter_wall_p99_ms": p99 * 1e3,
        "decode_iter_jitter_ms": (p99 - p50) * 1e3,
        "tokens_per_s": toks / wall,
        "tokens_per_work_unit": toks / (wall / med),
        "iterations": eng.iterations,
        "chunk_dispatches": int(getattr(eng.backend,
                                        "prefill_chunk_dispatches", 0)),
        "outputs": {r.rid: list(r.output) for r in reqs},
    }


def _chunked_prefill_contrast(arch, params, cfg):
    """Monolithic vs chunked admission on the identical adversarial
    workload (float arch → token-identical by construction). Jitter is
    the median across three trials per mode — a single lucky/stalled
    trial must not decide a latency claim. The throughput ratio uses raw
    tokens per stall-clipped wall second: the work-unit normalization the
    qos contrast uses divides by the run's own median iteration, and the
    chunked median *includes* chunk work — the two modes' work units are
    not the same size, so their ratio would overstate chunking."""
    out = {}
    for mode, chunk in (("unchunked", None), ("chunked", CHK_CHUNK)):
        trials = [_chunked_prefill_run(arch, params, cfg, chunk)
                  for _ in range(3)]
        best = dict(max(trials, key=lambda t: t["tokens_per_s"]))
        for key in ("decode_iter_jitter_ms", "iter_wall_p99_ms",
                    "tokens_per_s"):
            best[key] = float(np.median([t[key] for t in trials]))
        out[mode] = best
    assert out["chunked"]["outputs"] == out["unchunked"]["outputs"], (
        "chunked prefill diverged from monolithic on the bench workload")
    for mode in out:
        del out[mode]["outputs"]
    assert out["unchunked"]["chunk_dispatches"] == 0
    assert out["chunked"]["chunk_dispatches"] >= CHK_BE_N * (
        CHK_PROMPT // CHK_CHUNK)
    return {
        "arch": cfg.name,
        "slots": CHK_SLOTS,
        "prompt_tokens": CHK_PROMPT,
        "chunk_tokens": CHK_CHUNK,
        "be_requests": CHK_BE_N,
        "rt_requests": CHK_RT_N,
        "unchunked": out["unchunked"],
        "chunked": out["chunked"],
        "jitter_ratio": out["unchunked"]["decode_iter_jitter_ms"]
        / out["chunked"]["decode_iter_jitter_ms"],
        "tokens_per_s_ratio": out["chunked"]["tokens_per_s"]
        / out["unchunked"]["tokens_per_s"],
    }


SPEC_SLOTS = 4
SPEC_REQUESTS = 12
SPEC_NEW = 224        # long decodes: the win is iteration-count reduction,
SPEC_MAX_LEN = 256    # and the drafter deepens as the repeated tail grows
SPEC_K = 6
SPEC_SCALE = 2e-3     # weight shrink that makes greedy outputs repetitive
SPEC_TRIALS = 5       # best-of walls per mode: the host wall is noisy


def _spec_workload(cfg):
    """Periodic prompts for the speculative contrast: each request's
    20-token prompt tiles a random period-3 pattern, so the trailing
    n-gram always has earlier occurrences for the lookup drafter."""
    rng = np.random.default_rng(9)
    prompts = []
    for _ in range(SPEC_REQUESTS):
        tile = rng.integers(0, cfg.vocab, size=3).astype(np.int32)
        prompts.append(np.tile(tile, 8)[:20])
    return prompts


def _speculative_run(arch, params, cfg, prompts, k):
    from repro.serve.engine import EngineConfig, PagedServeEngine

    # admit_batch=1 keeps the prefill batch dimension constant, and the
    # huge admit_window disables forced admissions: a forced admission
    # preempts a running slot, and the resumed request re-prefills at
    # prompt+output tokens — a different pow2 bucket → a fresh trace in
    # the timed section
    ec = EngineConfig(slots=SPEC_SLOTS, max_len=SPEC_MAX_LEN,
                      block_len=BLOCK_LEN, backend="paged",
                      spec_tokens=k, admit_batch=1,
                      admit_window=100_000)
    eng = PagedServeEngine(arch, params, ec)
    # warm both traces (prefill bucket + decode/verify) off the clock
    for i in range(2):
        eng.add_request(prompts[i], max_new_tokens=SPEC_NEW,
                        rid=10_000 + i)
    eng.run_until_drained()
    traces0 = eng.decode_traces + eng.prefill_traces
    for rid, p in enumerate(prompts):
        eng.add_request(p, max_new_tokens=SPEC_NEW, rid=rid)
    iter_s = []
    for _ in range(10_000):
        if eng.idle:
            break
        it0 = time.perf_counter()
        eng.step()
        iter_s.append(time.perf_counter() - it0)
    assert eng.idle, "speculative run failed to drain"
    assert eng.decode_traces + eng.prefill_traces == traces0, (
        "speculative timed section retraced")
    outs = {rid: list(eng.request(rid).output)
            for rid in range(SPEC_REQUESTS)}
    assert all(len(o) == SPEC_NEW for o in outs.values())
    iter_s = np.asarray(iter_s)
    # stall-robust wall clock, same clip as the qos/chunked runs
    wall = float(np.minimum(iter_s, 50 * np.median(iter_s)).sum())
    return outs, wall, len(iter_s), eng.metrics()


def _speculative_contrast(arch, params, cfg):
    """Plain paged decode vs spec_tokens=K on a repetitive-text workload
    (float arch → token-identical by the acceptance contract). The smoke
    model's random weights produce an incompressible token stream no
    lookup drafter can predict, so shrink them toward zero: near-uniform
    logits make greedy settle into short cycles — the random-weight
    stand-in for the boilerplate/code-completion text speculative
    decoding targets. Best-of-``SPEC_TRIALS`` walls per mode: the
    contrast is a throughput ratio and a single stalled trial must not
    decide it."""
    import jax

    params_rep = jax.tree.map(lambda x: x * SPEC_SCALE, params)
    prompts = _spec_workload(cfg)
    out = {}
    for mode, k in (("off", 0), ("on", SPEC_K)):
        trials = [_speculative_run(arch, params_rep, cfg, prompts, k)
                  for _ in range(SPEC_TRIALS)]
        outs0 = trials[0][0]
        assert all(t[0] == outs0 for t in trials[1:]), (
            f"speculative {mode} trials diverged")
        wall = min(t[1] for t in trials)
        out[mode] = {"outs": outs0, "wall": wall,
                     "iterations": trials[0][2], "metrics": trials[0][3]}
    assert out["on"]["outs"] == out["off"]["outs"], (
        "speculative decoding diverged from the plain paged engine")
    toks = SPEC_REQUESTS * SPEC_NEW
    m_on = out["on"]["metrics"]
    drafted = int(m_on["spec_drafted"])
    accepted = int(m_on["spec_accepted"])
    tok_s_off = toks / out["off"]["wall"]
    tok_s_on = toks / out["on"]["wall"]
    return {
        "arch": cfg.name,
        "slots": SPEC_SLOTS,
        "requests": SPEC_REQUESTS,
        "max_new": SPEC_NEW,
        "spec_tokens": SPEC_K,
        "spec_method": "ngram",
        "tokens_per_s_off": tok_s_off,
        "tokens_per_s_on": tok_s_on,
        "tokens_per_s_per_slot_off": tok_s_off / SPEC_SLOTS,
        "tokens_per_s_per_slot_on": tok_s_on / SPEC_SLOTS,
        "tokens_per_s_ratio": tok_s_on / tok_s_off,
        "accept_rate": accepted / max(drafted, 1),
        "spec_drafted": drafted,
        "spec_accepted": accepted,
        "iterations_off": out["off"]["iterations"],
        "iterations_on": out["on"]["iterations"],
        "token_identical": True,
    }


def main(csv: bool = True):
    import jax

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import registry, schema as schema_lib
    from repro.serve.engine import (
        BatchedServeEngine, EngineConfig, PagedServeEngine, ServeEngine,
        metrics,
    )

    enable_compile_cache()
    cfg = configs.smoke_config("phi3-mini-3.8b")
    arch = registry.build(cfg)
    params = schema_lib.init_params(arch.schema(), jax.random.key(0))
    ec = EngineConfig(slots=SLOTS, max_len=MAX_LEN, block_len=BLOCK_LEN)

    rows = []
    results = {}
    for name, engine_cls in (("per_slot", ServeEngine),
                             ("batched", BatchedServeEngine),
                             ("paged", PagedServeEngine)):
        eng = engine_cls(arch, params, ec)
        done, wall, iter_s = _drive(eng, _workload(cfg))
        m = metrics(done)
        toks = sum(len(r.output) for r in done)
        results[name] = {
            "engine": eng, "metrics": m, "wall": wall,
            "tokens_per_s": toks / wall,
            "p50_ms": float(np.percentile(iter_s, 50) * 1e3),
            "p99_ms": float(np.percentile(iter_s, 99) * 1e3),
        }
        rows.append((
            f"serve_{name}", wall * 1e6 / max(eng.iterations, 1),
            f"tok_s={toks / wall:.1f}|ttft_ms={m['ttft_avg_s'] * 1e3:.1f}|"
            f"p50_ms={results[name]['p50_ms']:.1f}|"
            f"p99_ms={results[name]['p99_ms']:.1f}|"
            f"iters={eng.iterations}|dispatch={eng.decode_dispatches}|"
            f"xfer={eng.transfers}|retrace_dec={eng.decode_traces}|"
            f"retrace_pre={eng.prefill_traces}",
        ))

    # capacity at a fixed KV budget: dense reserves SLOTS·MAX_LEN tokens;
    # give the paged pool the same budget and 4x the decode rows
    budget_tokens = SLOTS * MAX_LEN
    ec_cap = EngineConfig(
        slots=4 * SLOTS, max_len=MAX_LEN, block_len=BLOCK_LEN,
        num_blocks=budget_tokens // BLOCK_LEN + 1)
    cap_eng = PagedServeEngine(arch, params, ec_cap)
    cap_done, cap_wall, _ = _drive(cap_eng, _short_workload(cfg))
    capacity_ratio = cap_eng.max_concurrent / SLOTS
    rows.append((
        "serve_paged_capacity", cap_wall * 1e6 / max(cap_eng.iterations, 1),
        f"budget_tokens={budget_tokens}|dense_slots={SLOTS}|"
        f"paged_concurrent={cap_eng.max_concurrent}|"
        f"ratio={capacity_ratio:.2f}x (claim: >=2x)",
    ))

    # sliding-window (ring-block) capacity: a windowed model serves on the
    # paged engine with per-L-layer pools bounded by the window; greedy
    # output must match the dense arena engine token-for-token
    from repro.models.cache import ring_blocks_for

    sw_cfg = configs.smoke_config("gemma3-4b")      # LLLLLG, window 16
    sw_arch = registry.build(sw_cfg)
    sw_params = schema_lib.init_params(sw_arch.schema(), jax.random.key(0))
    sw_ec = EngineConfig(slots=4, max_len=MAX_LEN, block_len=BLOCK_LEN)
    def sw_work():       # fresh identical workload per engine
        return _workload(sw_cfg, seed=3)[:12]

    sw_dense = BatchedServeEngine(sw_arch, sw_params, sw_ec)
    for r in sw_work():
        sw_dense.submit(r)
    sw_dense_out = {r.rid: list(r.output)
                    for r in sw_dense.run_until_drained()}
    sw_eng = PagedServeEngine(sw_arch, sw_params, sw_ec)
    sw_done, sw_wall, _ = _drive(sw_eng, sw_work())
    sw_out = {r.rid: list(r.output) for r in sw_done}
    assert sw_eng.ring, "sliding-window run did not use ring blocks"
    assert sw_out == sw_dense_out, "ring-block serving diverged from dense"
    wb = ring_blocks_for(sw_cfg.local_window, BLOCK_LEN)
    assert sw_eng.layout.ring_blocks == wb
    assert sw_eng.layout.ring_num_blocks == 1 + sw_ec.slots * wb
    ring_tokens = wb * BLOCK_LEN
    sliding = {
        "arch": sw_cfg.name,
        "local_window": sw_cfg.local_window,
        "max_len": sw_ec.max_len,
        "block_len": BLOCK_LEN,
        "ring_blocks_per_slot": wb,
        "ring_pool_blocks": sw_eng.layout.ring_num_blocks,
        "full_pool_blocks": sw_eng.layout.num_blocks,
        "ring_tokens_per_slot": ring_tokens,
        "dense_tokens_per_slot": sw_ec.max_len,
        "sliding_layer_residency_ratio": sw_ec.max_len / ring_tokens,
        "tokens_per_s": sum(len(r.output) for r in sw_done) / sw_wall,
        "token_identical_to_dense": True,
    }
    rows.append((
        "serve_paged_sliding_window", sw_wall * 1e6 / max(sw_eng.iterations, 1),
        f"window={sw_cfg.local_window}|ring_blocks/slot={wb}|"
        f"L-residency={ring_tokens} vs dense {sw_ec.max_len} tokens/slot "
        f"({sliding['sliding_layer_residency_ratio']:.1f}x smaller)|"
        f"identical=yes",
    ))

    # int8 block capacity: the quantized arch stores K/V natively as int8
    # blocks (+ per-block scales) — roughly half the pool bytes per token
    # of the float-block layout — so the SAME pool byte budget admits ~2x
    # the concurrent short requests. The float-block baseline is the same
    # model with serve_quant off (identical pool geometry, bf16 blocks).
    import dataclasses

    assert cfg.serve_quant, "int8 capacity run needs the quantized arch"
    arch_f = registry.build(dataclasses.replace(cfg, serve_quant=False))
    cap_ec = dict(max_len=MAX_LEN, block_len=BLOCK_LEN, admit_batch=4)
    float_eng = PagedServeEngine(arch_f, params, EngineConfig(
        slots=4 * SLOTS, num_blocks=budget_tokens // BLOCK_LEN + 1,
        **cap_ec))
    budget_bytes = float_eng.pool_bytes
    # size the int8 pool to the float pool's byte budget (per-block bytes
    # measured off a probe engine; pools scale linearly in num_blocks)
    probe = PagedServeEngine(arch, params, EngineConfig(
        slots=2, num_blocks=9, **cap_ec))
    per_block_i8 = probe.pool_bytes / probe.layout.num_blocks
    i8_eng = PagedServeEngine(arch, params, EngineConfig(
        slots=6 * SLOTS, num_blocks=int(budget_bytes // per_block_i8),
        **cap_ec))
    assert i8_eng.quantized and not float_eng.quantized
    assert i8_eng.pool_bytes <= budget_bytes
    f_done, f_wall, _ = _drive(float_eng, _short_workload(cfg, seed=2, n=64))
    i8_done, i8_wall, _ = _drive(i8_eng, _short_workload(cfg, seed=2, n=64))
    assert len(f_done) == len(i8_done) == 64
    i8_ratio = i8_eng.max_concurrent / max(float_eng.max_concurrent, 1)

    # identity spot check: the int8 block pool decodes token-identically
    # to the dense int8 reference (the full matrix lives in
    # tests/test_serve_paged.py; the sliding run above already asserted it
    # for the windowed arch)
    id_ec = EngineConfig(slots=4, max_len=MAX_LEN, block_len=BLOCK_LEN)
    id_dense = BatchedServeEngine(arch, params, id_ec)
    for r in _short_workload(cfg, seed=5, n=10):
        id_dense.submit(r)
    id_dense_out = {r.rid: list(r.output)
                    for r in id_dense.run_until_drained()}
    id_paged = PagedServeEngine(arch, params, id_ec)
    for r in _short_workload(cfg, seed=5, n=10):
        id_paged.submit(r)
    id_paged_out = {r.rid: list(r.output)
                    for r in id_paged.run_until_drained()}
    assert id_paged_out == id_dense_out, (
        "int8 block pool diverged from the dense int8 reference")

    int8_blocks = {
        "arch": cfg.name,
        "block_len": BLOCK_LEN,
        "budget_bytes": int(budget_bytes),
        "bytes_per_token_float": float_eng.pool_bytes_per_token,
        "bytes_per_token_int8": i8_eng.pool_bytes_per_token,
        "bytes_per_token_ratio": (float_eng.pool_bytes_per_token
                                  / i8_eng.pool_bytes_per_token),
        "pool_tokens_float": float_eng.layout.usable_tokens,
        "pool_tokens_int8": i8_eng.layout.usable_tokens,
        "float_concurrent_slots": float_eng.max_concurrent,
        "int8_concurrent_slots": i8_eng.max_concurrent,
        "capacity_ratio": i8_ratio,
        "tokens_per_s": sum(len(r.output) for r in i8_done) / i8_wall,
        "token_identical_to_dense_int8": True,
    }
    rows.append((
        "serve_paged_int8_blocks", i8_wall * 1e6 / max(i8_eng.iterations, 1),
        f"budget_bytes={int(budget_bytes)}|"
        f"B/token={int8_blocks['bytes_per_token_float']:.0f}->"
        f"{int8_blocks['bytes_per_token_int8']:.0f} "
        f"({int8_blocks['bytes_per_token_ratio']:.2f}x smaller)|"
        f"concurrent={float_eng.max_concurrent}->{i8_eng.max_concurrent} "
        f"({i8_ratio:.2f}x, claim: >=1.8x)|identical=yes",
    ))

    # prefix caching: shared-system-prompt workload, cache-on vs
    # cache-off, on the float arch (int8 resumes attend over dequantized
    # prefix K/V, a larger documented numerics caveat pinned by its own
    # tests) — admission prefills only the uncached suffix, and TTFT is
    # prefill-bound so skipping the shared blocks shows up directly.
    # Outputs are identical up to certified float near-ties (the
    # suffix-resume attention sums in a different order than the wide
    # full prefill).
    prefix_cache = _prefix_cache_contrast(arch_f, params, cfg)
    rows.append((
        "serve_paged_prefix_cache", 0.0,
        f"shared={prefix_cache['shared_fraction']:.0%} of "
        f"{PFX_REQUESTS} reqs x "
        f"{prefix_cache['shared_prefix_tokens']}-tok prefix|"
        f"ttft_ms={prefix_cache['ttft_avg_ms_off']:.1f}->"
        f"{prefix_cache['ttft_avg_ms_on']:.1f} "
        f"({prefix_cache['ttft_reduction']:.2f}x lower, claim: >=1.5x)|"
        f"hit_rate={prefix_cache['hit_rate']:.2f}|"
        f"skipped={prefix_cache['prefill_tokens_skipped']:.0f} tok|"
        f"near_tie_flips={prefix_cache['near_tie_flips']}",
    ))

    # chunked prefill: bounded decode-iteration jitter under adversarial
    # long-prompt admissions (float arch: chunked output is exactly
    # monolithic's; the int8 chunk-boundary near-tie contract is pinned
    # by its own tests)
    chunked_prefill = _chunked_prefill_contrast(arch_f, params, cfg)
    rows.append((
        "serve_paged_chunked_prefill", 0.0,
        f"{CHK_BE_N} x {CHK_PROMPT}-tok be prompts vs {CHK_RT_N} rt "
        f"decodes|jitter_ms="
        f"{chunked_prefill['unchunked']['decode_iter_jitter_ms']:.2f}->"
        f"{chunked_prefill['chunked']['decode_iter_jitter_ms']:.2f} "
        f"({chunked_prefill['jitter_ratio']:.1f}x lower, claim: >=4x)|"
        f"tok_s_ratio={chunked_prefill['tokens_per_s_ratio']:.3f} "
        f"(claim: >=0.9)|chunk={CHK_CHUNK}|identical=yes",
    ))

    # speculative decoding: n-gram drafts + small-q verify vs plain
    # decode on a repetitive-text workload (float arch: greedy acceptance
    # makes spec_tokens=k token-identical to k=0, asserted inside)
    speculative = _speculative_contrast(arch_f, params, cfg)
    rows.append((
        "serve_paged_speculative", 0.0,
        f"k={SPEC_K}|tok_s="
        f"{speculative['tokens_per_s_off']:.1f}->"
        f"{speculative['tokens_per_s_on']:.1f} "
        f"({speculative['tokens_per_s_ratio']:.2f}x, claim: >=1.3x)|"
        f"accept={speculative['accept_rate']:.2f}|"
        f"iters={speculative['iterations_off']}->"
        f"{speculative['iterations_on']}|identical=yes",
    ))

    # QoS traffic classes: rt-vs-be TTFT under full be contention, FCFS
    # vs the two-class QoS scheduler (same workload, same backend)
    qos_classes = _qos_contention(arch, params, cfg)
    rows.append((
        "serve_qos_classes", 0.0,
        f"rt_p99_ttft_ms={qos_classes['fcfs']['rt_p99_ms']:.1f}(fcfs)->"
        f"{qos_classes['qos']['rt_p99_ms']:.1f}(qos) "
        f"({qos_classes['rt_p99_improvement']:.1f}x lower, claim: >=4x)|"
        f"tok_s_ratio={qos_classes['tokens_per_s_ratio']:.3f} "
        f"(claim: within 10%)|"
        f"preemptions={qos_classes['qos']['preemptions']}",
    ))

    bat, ref, pag = results["batched"], results["per_slot"], results["paged"]
    speedup = bat["tokens_per_s"] / ref["tokens_per_s"]
    rows.append(("serve_speedup", 0.0,
                 f"{speedup:.2f}x (claim: >=3x at {SLOTS} slots)"))
    if csv:
        for r in rows:
            print(f"{r[0]},{r[1]:.1f},{r[2]}")

    try:
        with open("BENCH_serve.json") as f:
            previous_mesh = json.load(f).get("paged", {}).get(
                "mesh_scaling")
    except FileNotFoundError:
        previous_mesh = None
    with open("BENCH_serve.json", "w") as f:
        json.dump({
            "name": "serve_batched",
            "tokens_per_s": bat["tokens_per_s"],
            "ttft_avg_s": bat["metrics"]["ttft_avg_s"],
            "retrace_count": (bat["engine"].decode_traces
                              + bat["engine"].prefill_traces),
            "paged": {
                "tokens_per_s": pag["tokens_per_s"],
                "ttft_avg_s": pag["metrics"]["ttft_avg_s"],
                "block_len": BLOCK_LEN,
                "budget_tokens": budget_tokens,
                "dense_concurrent_slots": SLOTS,
                "paged_concurrent_slots": cap_eng.max_concurrent,
                "capacity_ratio": capacity_ratio,
                "sliding_window": sliding,
                "int8_blocks": int8_blocks,
                "prefix_cache": prefix_cache,
                "chunked_prefill": chunked_prefill,
                "speculative": speculative,
                # written by --mesh-scaling, which runs in a process of
                # its own; kept across this run's rewrite
                **({"mesh_scaling": previous_mesh}
                   if previous_mesh is not None else {}),
            },
            "qos_classes": qos_classes,
        }, f, indent=2)

    for name in ("batched", "paged"):
        eng = results[name]["engine"]
        # the QoS dataflow contract: one batched decode dispatch and one
        # device→host fetch per engine iteration — never per slot
        assert eng.decode_dispatches <= eng.iterations, (
            f"{name}: extra decode dispatch")
        assert eng.transfers <= eng.iterations, (
            f"{name}: extra device→host transfer")
    assert bat["engine"].prefill_traces < ref["engine"].prefill_traces, (
        "bucketing did not reduce prefill retraces")
    assert speedup >= 3.0, (
        f"batched engine {speedup:.2f}x < 3x over per-slot baseline")
    assert capacity_ratio >= 2.0, (
        f"paged pool admitted only {capacity_ratio:.2f}x the dense slots "
        f"at an equal KV budget")
    assert i8_ratio >= 1.8, (
        f"int8 block pool admitted only {i8_ratio:.2f}x the float-block "
        f"slots at an equal pool byte budget")
    assert prefix_cache["ttft_reduction"] >= 1.5, (
        f"prefix caching lowered mean TTFT only "
        f"{prefix_cache['ttft_reduction']:.2f}x on a "
        f"{prefix_cache['shared_fraction']:.0%}-shared workload "
        f"(claim: >=1.5x)")
    assert chunked_prefill["jitter_ratio"] >= 4.0, (
        f"chunked prefill lowered p99 decode-iteration jitter only "
        f"{chunked_prefill['jitter_ratio']:.2f}x vs monolithic admission "
        f"(claim: >=4x)")
    assert chunked_prefill["tokens_per_s_ratio"] >= 0.9, (
        f"chunked prefill cost {chunked_prefill['tokens_per_s_ratio']:.3f}x "
        f"the monolithic aggregate throughput (claim: >=0.9x)")
    assert speculative["tokens_per_s_ratio"] >= 1.3, (
        f"speculative decoding won only "
        f"{speculative['tokens_per_s_ratio']:.2f}x tokens/s per slot over "
        f"plain paged decode on the repetitive workload (claim: >=1.3x)")
    assert qos_classes["rt_p99_improvement"] >= 4.0, (
        f"QoS scheduler lowered rt p99 TTFT only "
        f"{qos_classes['rt_p99_improvement']:.2f}x vs FCFS (claim: >=4x)")
    assert 0.9 <= qos_classes["tokens_per_s_ratio"] <= 1.1, (
        f"QoS run's aggregate throughput drifted "
        f"{qos_classes['tokens_per_s_ratio']:.3f}x from FCFS "
        f"(claim: equal within 10%)")
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description="serve-engine benchmark")
    ap.add_argument("--mesh-scaling", action="store_true",
                    help="run only the mesh capacity sweep, over the "
                         "devices this process holds")
    if ap.parse_args().mesh_scaling:
        mesh_main()
    else:
        main()
