"""Serving launcher: ``python -m repro.launch.serve --arch <id> …``.

Constructs the one serve front-end, ``repro.serve.LLMEngine``, from a
``--backend`` (execution) × ``--scheduler`` (admission policy) pair:

  * backends: ``arena`` (vectorized dense arena, default), ``paged``
    (shared block-pool KV), ``slot`` (sequential per-slot reference);
  * schedulers: ``bounded`` (default), ``fcfs``, ``qos`` (two traffic
    classes — ``--rt-fraction`` marks that share of requests as ``"rt"``
    latency-critical; the rest are best-effort).

``--engine batched|paged|reference`` is kept as a deprecated alias for
``--backend``.

:func:`build_engine` is the construction both this launcher and
``chip_smoke.py`` use: seeded random weights in the config's dtype and,
on a device that reports its memory, a paged pool sized to what the
weights leave (``PagedBackend`` fits it).
"""

from __future__ import annotations

import argparse

import numpy as np

# name lists live in repro.serve.config (the single source of truth);
# the deprecated --engine names are that module's legacy aliases too
from repro.serve.config import (
    BACKENDS, SCHEDULERS, SPEC_METHODS, canonical_backend,
)

_ENGINE_NAMES = ("batched", "paged", "reference")


def build_engine(model, ec, *, mesh=None) -> "LLMEngine":
    """``LLMEngine`` for ``model`` with random weights from seed 0 in the
    config's own dtype, replicated over ``mesh`` when one is given. With
    the paged backend and ``ec.num_blocks=None`` the backend fits the
    pool to the device's memory (``PagedBackend.pool_sizing``)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.models import registry, schema as schema_lib
    from repro.serve import LLMEngine

    arch = registry.build(model)
    if ec.backend not in arch.serve_backends:
        raise ValueError(
            f"backend {ec.backend} unsupported for {model.name} "
            f"(family {model.family}): supported = "
            f"{', '.join(arch.serve_backends)}")
    rep = None if mesh is None else NamedSharding(mesh, PartitionSpec())
    params = schema_lib.init_params(arch.schema(), jax.random.key(0),
                                    dtype=model.compute_dtype, sharding=rep)
    return LLMEngine(arch, params, ec, mesh=mesh)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--backend", choices=BACKENDS,
                    default=None, help="execution backend (CacheBackend)")
    ap.add_argument("--engine", choices=_ENGINE_NAMES,
                    default=None,
                    help="DEPRECATED alias for --backend "
                         "(batched→arena, reference→slot)")
    ap.add_argument("--scheduler", choices=SCHEDULERS,
                    default="bounded", help="admission policy")
    ap.add_argument("--rt-fraction", type=float, default=0.0,
                    help="fraction of requests submitted as the 'rt' "
                         "(latency-critical) QoS class; the qos scheduler "
                         "guarantees their admission window")
    ap.add_argument("--rt-window", type=int, default=2,
                    help="qos scheduler: max iterations an rt lane head "
                         "may wait before a forced admission")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--admit-window", type=int, default=8)
    ap.add_argument("--admit-batch", type=int, default=1,
                    help="max admissions per iteration (cold-start ramp "
                         "reaches full concurrency in slots/admit_batch "
                         "iterations)")
    ap.add_argument("--block-len", type=int, default=16,
                    help="KV block size (paged backend)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size incl. trash block (paged backend; "
                         "default matches the dense arena budget; "
                         "sliding-window layers use a separate ring arena "
                         "bounded by the window)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 enables on-device sampling "
                         "(vectorized backends)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-addressed KV block reuse across "
                         "requests (paged backend, full-history layouts)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared system-prompt tokens "
                         "to every request (shows prefix-cache hits)")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=None,
                    help="co-schedule prefill with decode in chunks of "
                         "this many tokens per iteration (paged backend; "
                         "multiple of --block-len; default monolithic)")
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="speculative decoding: host-drafted tokens "
                         "verified per iteration (paged backend; 0 = off; "
                         "greedy acceptance stays token-identical)")
    ap.add_argument("--spec-method", choices=SPEC_METHODS, default="ngram",
                    help="draft method: 'ngram' = prompt-lookup matching "
                         "over the request's own tokens (no second model)")
    ap.add_argument("--be-token-share", type=float, default=None,
                    help="qos scheduler: cap the best-effort share of "
                         "decode tokens while rt traffic waits (0, 1)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the paged KV pool over this many devices "
                         "(0 = no mesh; run under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N on CPU)")
    ap.add_argument("--kv-shard", choices=("auto", "heads", "blocks"),
                    default="auto",
                    help="mesh sharding strategy: 'heads' slices the "
                         "KV-head axis (bit-identical), 'blocks' gives "
                         "each device a slice of the block pool; 'auto' "
                         "picks heads when the head count divides --mesh")
    args = ap.parse_args()

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve import EngineConfig, metrics

    enable_compile_cache()
    backend = canonical_backend(args.backend or args.engine or "batched")
    model = (configs.smoke_config(args.arch) if args.smoke
             else configs.get_config(args.arch))
    ec = EngineConfig(slots=args.slots, max_len=args.max_len,
                      admit_window=args.admit_window,
                      admit_batch=args.admit_batch,
                      greedy=args.temperature <= 0,
                      temperature=max(args.temperature, 1e-6),
                      block_len=args.block_len, num_blocks=args.num_blocks,
                      backend=backend, scheduler=args.scheduler,
                      rt_window=args.rt_window,
                      prefix_cache=args.prefix_cache,
                      prefill_chunk_tokens=args.prefill_chunk_tokens,
                      spec_tokens=args.spec_tokens,
                      spec_method=args.spec_method,
                      be_token_share=args.be_token_share,
                      kv_shard=args.kv_shard)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(args.mesh)
    try:
        engine = build_engine(model, ec, mesh=mesh)
    except ValueError as e:
        raise SystemExit(str(e))
    sizing = getattr(engine.backend, "pool_sizing", None)
    if sizing:
        print(f"pool: {sizing['num_blocks']} blocks of "
              f"{sizing['block_bytes']} B per device (memory fits "
              f"{sizing['fit_blocks']}, the config addresses "
              f"{sizing['want_blocks']})")
    if mesh is not None:
        em = engine.metrics()
        per_dev = {k: v for k, v in em.items()
                   if k.startswith("pool_bytes_dev")}
        print(f"mesh: {engine.ndev} devices, kv_shard={engine.kv_mode}, "
              f"pool {em['pool_bytes_total'] / 2**20:.2f} MiB total, "
              f"per-device "
              + " ".join(f"{k.removeprefix('pool_bytes_')}="
                         f"{v / 2**20:.2f}MiB"
                         for k, v in sorted(per_dev.items())))
    rng = np.random.default_rng(0)
    shared = rng.integers(0, model.vocab,
                          size=args.shared_prefix).astype(np.int32)
    handles = []
    for rid in range(args.requests):
        prompt = rng.integers(0, model.vocab,
                              size=rng.integers(4, 32)).astype(np.int32)
        handles.append(engine.add_request(
            np.concatenate([shared, prompt]),
            max_new_tokens=args.max_new,
            qos="rt" if rng.random() < args.rt_fraction else "be"))
    done = engine.run_until_drained()
    print(metrics(done))
    if args.prefix_cache:
        em = engine.metrics()
        print("prefix_cache: " + " ".join(
            f"{k.removeprefix('prefix_cache_')}="
            f"{em[k]:.3f}" if isinstance(em[k], float) else
            f"{k.removeprefix('prefix_cache_')}={em[k]}"
            for k in sorted(em) if "prefix" in k or "prefill" in k))
    if args.spec_tokens:
        em = engine.metrics()
        print("speculative: " + " ".join(
            f"{k}={em[k]:.3f}" if isinstance(em[k], float) else
            f"{k}={em[k]}"
            for k in sorted(em)
            if k.startswith("spec_") or "per_token" in k))
    if args.prefill_chunk_tokens:
        em = engine.metrics()
        print("chunked_prefill: " + " ".join(
            f"{k}={em[k]:.3f}" if isinstance(em[k], float) else
            f"{k}={em[k]}"
            for k in sorted(em)
            if "chunk" in k or "jitter" in k or "iter_wall" in k))
    by_class = {}
    for h in handles:
        r = engine.request(h)
        if r.first_token_at is not None:
            by_class.setdefault(r.qos, []).append(
                r.first_token_at - r.submitted_at)
    for qos, ttfts in sorted(by_class.items()):
        print(f"ttft[{qos}]: avg {np.mean(ttfts) * 1e3:.1f} ms "
              f"p99 {np.percentile(ttfts, 99) * 1e3:.1f} ms "
              f"({len(ttfts)} requests)")
    print(f"iters={engine.iterations} dispatches={engine.decode_dispatches} "
          f"transfers={engine.transfers} "
          f"traces(decode/prefill)={engine.decode_traces}/"
          f"{engine.prefill_traces}")


if __name__ == "__main__":
    main()
