"""Benchmark orchestrator — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Sections:
  fig6a   — multi-cluster matmul scaling, interleaved vs baseline (2×)
  fig6b   — QoS narrow-latency under bursts (16×, 34-cycle worst case)
  table1  — peak perf/efficiency incl. Fig. 7 L1/L2 and Fig. 8b shmoo
  table2  — full-network energy/throughput (MobileBERT/Whisper/DINOv2)
  kernels — op-backend micro-benchmarks + bit-exactness
  serve   — per-slot vs batched vs paged serve engines (also writes
            BENCH_serve.json with the paged-vs-dense capacity comparison)
  serve_mesh — the mesh capacity sweep (``serve_bench.py --mesh-scaling``)
            in a process of its own with 8 devices, started before this
            process touches JAX: a chip belongs to one process at a time

``--smoke`` only imports every benchmark module (CI import check: catches
broken imports / renamed APIs without paying the full benchmark runtime).
"""

from __future__ import annotations

import importlib
import os
import sys
import traceback

# allow `python benchmarks/run.py` from the repo root (script mode puts
# benchmarks/ itself, not the repo root, on sys.path)
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

SECTIONS = [
    ("fig6a", "benchmarks.fig6a_multicluster"),
    ("fig6b", "benchmarks.fig6b_qos"),
    ("table1", "benchmarks.table1_efficiency"),
    ("table2", "benchmarks.table2_networks"),
    ("kernels", "benchmarks.kernel_bench"),
    ("serve", "benchmarks.serve_bench"),
]


# the serve package split (LLMEngine front-end / schedulers / backends /
# legacy shims): a bad module split should fail the smoke check, not the
# first real serving run
SERVE_MODULES = [
    "repro.serve",
    "repro.serve.request",
    "repro.serve.config",
    "repro.serve.scheduler",
    "repro.serve.spec",
    "repro.serve.backends",
    "repro.serve.api",
    "repro.serve.engine",
]


def smoke() -> None:
    """Import-check every benchmark module without running it, plus the
    serve package modules (and their public entry points) and the
    prefix-caching allocator surface."""
    failures = 0
    try:
        from repro.models import cache as _cache
        alloc = _cache.BlockAllocator(
            _cache.PagedLayout(block_len=4, num_blocks=4, max_len=16),
            prefix_cache=True)
        keys = _cache.prefix_chain_keys(list(range(8)), 4)
        for attr in ("lookup", "register", "ensure_writable", "incref",
                     "decref", "cached_blocks", "live_blocks",
                     "reclaimable_blocks", "hit_blocks", "cow_copies",
                     "evictions"):
            if not hasattr(alloc, attr):
                raise AttributeError(f"BlockAllocator.{attr} missing")
        if len(keys) != 2 or not callable(_cache.gather_prefix_kv):
            raise AttributeError("prefix-cache key/gather surface broken")
        print("repro.models.cache.prefix,0.0,import_ok")
    except Exception as e:  # noqa: BLE001
        failures += 1
        print(f"prefix_cache_IMPORT_ERROR,0.0,{type(e).__name__}:{e}")
        traceback.print_exc(file=sys.stderr, limit=3)
    try:
        from repro.launch.mesh import make_serve_mesh
        from repro.models.cache import KVShard
        from repro.parallel.sharding import (
            paged_cache_axes, pick_paged_serve_rules,
        )
        from repro.kernels.paged_attention.ref import (
            paged_attention_sharded_oracle,
        )
        from repro.serve.config import EngineConfig as _EC
        for fn in (make_serve_mesh, pick_paged_serve_rules,
                   paged_cache_axes, paged_attention_sharded_oracle,
                   KVShard):
            if not callable(fn):
                raise AttributeError(f"{fn!r} not callable")
        ec = _EC()
        for field in ("mesh_axes", "kv_shard"):
            if not hasattr(ec, field):
                raise AttributeError(f"EngineConfig.{field} missing")
        print("repro.serve.mesh_surface,0.0,import_ok")
    except Exception as e:  # noqa: BLE001
        failures += 1
        print(f"mesh_surface_IMPORT_ERROR,0.0,{type(e).__name__}:{e}")
        traceback.print_exc(file=sys.stderr, limit=3)
    try:
        from repro.kernels.paged_attention import (
            paged_attention_verify, paged_attention_verify_int8,
        )
        from repro.serve.spec import accept_tokens, ngram_propose
        for fn in (paged_attention_verify, paged_attention_verify_int8,
                   ngram_propose, accept_tokens):
            if not callable(fn):
                raise AttributeError(f"{fn!r} not callable")
        print("repro.serve.spec_surface,0.0,import_ok")
    except Exception as e:  # noqa: BLE001
        failures += 1
        print(f"spec_surface_IMPORT_ERROR,0.0,{type(e).__name__}:{e}")
        traceback.print_exc(file=sys.stderr, limit=3)
    try:
        from repro.analysis import (
            CHECKERS as _CHECKERS, hot_path as _hp, parse_pragmas as _pp,
        )
        from repro.analysis.cli import main as _analysis_main
        expected_rules = {"host-sync", "retrace-hazard", "pallas-index",
                          "alloc-pairing", "prng-key"}
        if set(_CHECKERS) != expected_rules:
            raise AttributeError(
                f"checker registry drifted: {sorted(_CHECKERS)}")
        if not callable(_hp) or not callable(_pp) \
                or not callable(_analysis_main):
            raise AttributeError("analysis entry points not callable")
        print("repro.analysis,0.0,import_ok")
    except Exception as e:  # noqa: BLE001
        failures += 1
        print(f"analysis_IMPORT_ERROR,0.0,{type(e).__name__}:{e}")
        traceback.print_exc(file=sys.stderr, limit=3)
    for mod in SERVE_MODULES:
        try:
            m = importlib.import_module(mod)
            if mod == "repro.serve.api" and not callable(
                    getattr(m, "LLMEngine", None)):
                raise AttributeError("repro.serve.api.LLMEngine missing")
            if mod == "repro.serve.config":
                for field in ("prefix_cache", "be_token_share",
                              "prefill_chunk_tokens", "spec_tokens",
                              "spec_method"):
                    if not hasattr(m.EngineConfig(), field):
                        raise AttributeError(
                            f"EngineConfig.{field} missing")
            if mod == "repro.serve.engine":
                for legacy in ("ServeEngine", "BatchedServeEngine",
                               "PagedServeEngine"):
                    if not callable(getattr(m, legacy, None)):
                        raise AttributeError(f"legacy shim {legacy} missing")
            print(f"{mod},0.0,import_ok")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{mod}_IMPORT_ERROR,0.0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr, limit=3)
    for label, mod in SECTIONS:
        try:
            m = importlib.import_module(mod)
            if not callable(getattr(m, "main", None)):
                raise AttributeError(f"{mod}.main is not callable")
            print(f"{label},0.0,import_ok")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{label}_IMPORT_ERROR,0.0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr, limit=3)
    if failures:
        print(f"FAILURES,{failures},see stderr")
        sys.exit(1)


def _serve_mesh() -> bool:
    """Run the mesh sweep in a child process with 8 devices (forced host
    devices on the CPU; on an accelerator, the chips it has). Called
    before any section imports JAX, so the child can hold the devices."""
    import subprocess

    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(_ROOT, "src"), env.get("PYTHONPATH", ""))
        if p)
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmarks", "serve_bench.py"),
         "--mesh-scaling"], env=env, capture_output=True, text=True)
    sys.stdout.write(proc.stdout)
    if proc.returncode:
        print(f"serve_mesh_CLAIM_FAILED,0.0,exit {proc.returncode}")
        sys.stderr.write(proc.stderr[-4000:])
    return proc.returncode == 0


def main() -> None:
    print("name,us_per_call,derived")
    failures = 0 if _serve_mesh() else 1
    for label, mod in SECTIONS:
        try:
            m = importlib.import_module(mod)
            m.main(csv=True)
        except AssertionError as e:
            failures += 1
            print(f"{label}_CLAIM_FAILED,0.0,{e}")
            traceback.print_exc(file=sys.stderr, limit=2)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{label}_ERROR,0.0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr, limit=3)
    if failures:
        print(f"FAILURES,{failures},see stderr")
        sys.exit(1)


if __name__ == "__main__":
    if "--smoke" in sys.argv[1:]:
        smoke()
    else:
        main()
