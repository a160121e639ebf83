"""Serve phi3-mini-3.8b at its published widths on a TPU, and check it.

    python chip_smoke.py                  # one chip: the int8 and bf16 phases
    python chip_smoke.py --chips 4        # the mesh path only, on four chips
    python chip_smoke.py --cpu-rehearsal  # smoke widths, kernels interpreted

The engine is the one ``python -m repro.launch.serve`` builds
(``repro.launch.serve.build_engine``): ``LLMEngine`` on the paged backend,
seeded random weights in the config's dtype, a pool sized to what the
weights leave of the chip's memory. Eight greedy requests, prompts in two
prefill buckets, 32 new tokens each.

One chip runs two phases in one process, each freeing its engine before
the next: the config as it stands (``serve_quant=True``: int8 pools and
W8A8 decode) and ``serve_quant=False`` (bf16 pools). Each phase's tokens
are checked against ``transformer.forward`` in float32 at
``default_matmul_precision("highest")``, teacher-forced on prompt + output
with the same weights: at every generated position, the reference logit
of the token the engine chose must lie within the phase's ``tol`` of the
reference maximum.

``--chips 4`` runs only the mesh path: a heads-sharded engine over a
4-device serve mesh (phi3-mini's 32 KV heads divide 4), on the same
requests, against the same engine on one chip in the same process. Heads
mode is bit-identical to one device, so the tokens must be equal.

The last line of standard output is one JSON object naming the device,
printed only when every check passed. Without a TPU the script exits
non-zero and names the platform it found. Every timing printed is one
unrepeated run, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from contextlib import contextmanager

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "phi3-mini-3.8b"
SEED = 0  # of the prompts; the weights come from build_engine's own seed
SLOTS = 8
BLOCK_LEN = 16

# Tolerances on (reference max logit - reference logit of the engine's
# token), in logits of the float32 reference. With these random weights a
# logit has a standard deviation of about 0.3 and the reference's top-2
# gap a median of about 0.05; a token drawn at random sits about 1.2
# below the maximum. Both were set before any chip run, from CPU runs of
# this check at the published widths cut to 2, 4 and 8 layers (16 new
# tokens, max_len 256): once with the XLA reference kernels, once with
# the Pallas kernels the TPU runs, through the interpreter. No deeper cut
# was run on the CPU (the interpreted 4-layer cut took about 25 minutes,
# and a deep cut's float32 reference is too large for a shared host);
# the chip run is the 32-layer reading.
# bf16: the same bf16 weights in both; the engine rounds activations and
#   K/V to bf16. Worst margins at 2, 4, 8 layers: 0.0036, 0.0005, 0.0069
#   (XLA) and 0.0036, 0.0082, 0.0059 (Pallas interpreted). They do not
#   grow steadily with depth; the tolerance is about four times the
#   largest. An engine at int8's precision fails it: int8 is past 0.06
#   at 2 layers already.
TOL_BF16 = 0.03
# int8: W8A8 GEMMs (per-channel weight scales, the static activation scale
#   4/127) and K/V stored with the static KV_SCALE. Worst margins 0.09,
#   0.17, 0.22 at 2, 4, 8 layers (XLA, integer softmax) and 0.066, 0.133,
#   0.205 (Pallas interpreted, f32 softmax); they grow with depth, and as
#   the square root of it they reach about 0.45 (XLA) or 0.41 (Pallas) at
#   32. Half a random token's distance from the maximum: it catches an
#   engine that attends the wrong blocks or mis-scales, not int8's noise.
TOL_INT8 = 0.6


@dataclasses.dataclass(frozen=True)
class Workload:
    short: tuple      # prompt-length range of the first prefill bucket
    long: tuple       # ... and of the second
    max_new: int
    max_len: int


FULL = Workload(short=(40, 64), long=(80, 128), max_new=32, max_len=512)
REHEARSAL = Workload(short=(5, 8), long=(9, 16), max_new=8, max_len=64)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or reading a
    compiled program back from the persistent cache)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextmanager
    def span(self, out: dict, key: str):
        s0, h0 = self.seconds, self.cache_hits
        yield
        out[key] = self.seconds - s0
        out[key + "_cache_hits"] = self.cache_hits - h0


def make_prompts(vocab: int, wl: Workload):
    rng = np.random.default_rng(SEED)
    prompts = []
    for lo, hi in (wl.short, wl.long):
        for _ in range(SLOTS // 2):
            n = int(rng.integers(lo, hi + 1))
            prompts.append(rng.integers(0, vocab, size=n).astype(np.int32))
    return prompts


def serve(model, wl: Workload, prompts, clock, *, mesh=None):
    """Build the engine, serve ``prompts`` greedily, release the engine.
    Returns (outputs, the params the engine served, report)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import resolve_backend
    from repro.launch.serve import build_engine
    from repro.serve import EngineConfig

    ec = EngineConfig(backend="paged", slots=SLOTS, max_len=wl.max_len,
                      block_len=BLOCK_LEN)
    rep: dict = {}
    t0 = time.perf_counter()
    engine = build_engine(model, ec, mesh=mesh)
    rep["build_s"] = time.perf_counter() - t0
    b = engine.backend
    rep["sizing"] = b.pool_sizing
    rep["num_blocks"] = b.layout.num_blocks
    rep["pool_bytes"] = b.pool_bytes
    rep["pool_bytes_by_device"] = b.pool_bytes_by_device()
    rep["kernels"] = {
        "paged_attention": b.attn_backend,
        "int8_gemm": (resolve_backend(None) if b.qparams is not None
                      else "not on this path (bf16 weights)"),
        "prefill_attention": "jnp chunked attention (no kernel)",
    }
    handles = [engine.add_request(p, max_new_tokens=wl.max_new)
               for p in prompts]
    t0 = time.perf_counter()
    with clock.span(rep, "serve_compile_s"):
        engine.run_until_drained()
    rep["serve_wall_s"] = time.perf_counter() - t0
    outputs = [np.asarray(engine.request(h).output, np.int32)
               for h in handles]
    if any(o.size != wl.max_new for o in outputs):
        fail(f"requests finished early: {[o.size for o in outputs]}")
    rep["prefill_traces"] = engine.prefill_traces
    rep["decode_traces"] = engine.decode_traces
    zeros = jnp.zeros((SLOTS,), jnp.int32)
    samp = (jnp.zeros((SLOTS,), jnp.float32), zeros, zeros, zeros)
    with clock.span(rep, "hlo_compile_s"):
        hlo = b.lower_decode(samp).compile().as_text()
    rep["decode_custom_calls"] = hlo.count("tpu_custom_call")
    if mesh is not None:
        rep["param_devices"] = sorted(
            {d.id for leaf in jax.tree.leaves(b.params)
             for d in leaf.sharding.device_set})
        rep["cache_devices"] = sorted(
            {d.id for leaf in jax.tree.leaves(b.cache)
             for d in leaf.sharding.device_set})
        rep["live_bytes"] = live_bytes_by_device(jax)
    params = engine.params
    del engine, b
    gc.collect()
    return outputs, params, rep


def reference_margins(model, params, prompts, outputs):
    """(margin, top-2 gap, random-pick margin) at every generated
    position, from the float32 teacher-forced reference: margin = max
    logit - logit of the engine's token, gap = max logit - second logit,
    random-pick margin = max logit - median logit (what a token drawn at
    random would score)."""
    import jax

    from repro.models import registry

    ref = registry.build(dataclasses.replace(model, dtype="float32",
                                             serve_quant=False))
    seqs = [np.concatenate([p, o]) for p, o in zip(prompts, outputs)]
    width = max(s.size for s in seqs)
    tokens = np.zeros((len(seqs), width), np.int32)  # right-pad: causal
    for i, s in enumerate(seqs):
        tokens[i, :s.size] = s
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(ref.forward)(params, tokens)
    margins, gaps, median = [], [], []
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        pos = np.arange(p.size - 1, p.size - 1 + o.size)
        lg = np.asarray(logits[i, pos], np.float32)        # [n, V]
        top2 = np.sort(lg, axis=-1)[:, -2:]
        margins.append(top2[:, 1] - lg[np.arange(o.size), o])
        gaps.append(top2[:, 1] - top2[:, 0])
        median.append(top2[:, 1] - np.median(lg, axis=-1))
    del logits
    gc.collect()
    return (np.concatenate(margins), np.concatenate(gaps),
            np.concatenate(median))


def device_line(jax):
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def live_bytes_by_device(jax) -> dict:
    """Bytes of every live array's shards, by device id."""
    out = {d.id: 0 for d in jax.devices()}
    for a in jax.live_arrays():
        for sh in a.addressable_shards:
            out[sh.device.id] += sh.data.nbytes
    return out


def peak_bytes(jax):
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("bytes_limit")


def widths(model) -> str:
    return (f"{model.name}: layers={model.n_layers} d_model={model.d_model} "
            f"heads={model.n_heads} kv_heads={model.n_kv_heads} "
            f"head_dim={model.hd} d_ff={model.d_ff} vocab={model.vocab} "
            f"dtype={model.dtype}")


def run_one_chip(model, wl, prompts, clock, jax, on_chip):
    for quant, tol in ((True, TOL_INT8), (False, TOL_BF16)):
        phase = "int8" if quant else "bf16"
        m = dataclasses.replace(model, serve_quant=quant)
        outputs, params, rep = serve(m, wl, prompts, clock)
        print(f"[{phase}] {widths(m)} serve_quant={quant}")
        print(f"[{phase}] pool: {rep['num_blocks']} blocks x {BLOCK_LEN} "
              f"tokens = {rep['pool_bytes'] / 2**30:.3f} GiB; fit "
              f"{rep['sizing'] or 'not fitted (device reports no memory)'}")
        print(f"[{phase}] kernels: {rep['kernels']}")
        print(f"[{phase}] compile seconds (one unrepeated run): serving "
              f"{rep['serve_compile_s']:.1f} "
              f"({rep['serve_compile_s_cache_hits']} persistent-cache hits),"
              f" decode-step lowering for inspection "
              f"{rep['hlo_compile_s']:.1f}; prefill traces "
              f"{rep['prefill_traces']}, decode traces "
              f"{rep['decode_traces']}")
        print(f"[{phase}] serve wall seconds incl. compile (one unrepeated "
              f"run, not a benchmark): {rep['serve_wall_s']:.1f}; engine "
              f"build {rep['build_s']:.1f}")
        peak, limit = peak_bytes(jax)
        print(f"[{phase}] memory: peak_bytes_in_use={peak} (process-wide "
              f"peak so far) bytes_limit={limit}")
        print(f"[{phase}] compiled decode step: "
              f"{rep['decode_custom_calls']} tpu_custom_call")
        if on_chip and rep["decode_custom_calls"] == 0:
            fail(f"[{phase}] the compiled decode step holds no Pallas "
                 f"custom call")
        if peak is not None and limit is not None and peak >= limit:
            fail(f"[{phase}] peak bytes {peak} reached the limit {limit}")
        margins, gaps, rand = reference_margins(m, params, prompts,
                                                outputs)
        del params
        gc.collect()
        q = np.quantile(gaps, [0.0, 0.1, 0.5, 0.9])
        print(f"[{phase}] reference top-2 gap over {gaps.size} positions: "
              f"min {q[0]:.4f} p10 {q[1]:.4f} p50 {q[2]:.4f} "
              f"p90 {q[3]:.4f}; {np.mean(gaps > tol):.2f} of positions "
              f"have a gap above tol, where a wrong pick fails; a token "
              f"drawn at random would sit {np.median(rand):.4f} below "
              f"the maximum (median)")
        worst = float(margins.max())
        print(f"[{phase}] reference check: worst margin {worst:.4f}, "
              f"{int(np.sum(margins > 0))} of {margins.size} picks not the "
              f"reference argmax, tol {tol}")
        if worst > tol:
            fail(f"[{phase}] engine token {worst:.4f} below the reference "
                 f"maximum, beyond tol {tol}")
        print(f"[{phase}] reference check passed")


def run_four_chips(model, wl, prompts, clock, jax, on_chip):
    from repro.launch.mesh import make_serve_mesh

    if len(jax.devices()) < 4:
        fail(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    print(f"[mesh] {widths(model)} serve_quant={model.serve_quant}")
    one, params1, rep1 = serve(model, wl, prompts, clock)
    del params1  # the one-chip weights would sit on device 0 beside
    gc.collect()  # the mesh engine's replica of them
    print(f"[mesh] one chip: pool {rep1['num_blocks']} blocks, compile "
          f"seconds {rep1['serve_compile_s']:.1f}, peak_bytes_in_use "
          f"{peak_bytes(jax)[0]}")
    mesh = make_serve_mesh(4)
    four, _, rep4 = serve(model, wl, prompts, clock, mesh=mesh)
    print(f"[mesh] four chips: pool {rep4['num_blocks']} blocks, per "
          f"device {rep4['pool_bytes_by_device']} B, params on devices "
          f"{rep4['param_devices']}, pool on devices "
          f"{rep4['cache_devices']}")
    print(f"[mesh] four chips: compile seconds (one unrepeated run) "
          f"{rep4['serve_compile_s']:.1f}, serve wall incl. compile "
          f"{rep4['serve_wall_s']:.1f}, decode step holds "
          f"{rep4['decode_custom_calls']} tpu_custom_call")
    stats = [d.memory_stats() or {} for d in jax.devices()[:4]]
    print(f"[mesh] peak_bytes_in_use per device: "
          f"{[s.get('peak_bytes_in_use') for s in stats]}")
    if len(rep4["cache_devices"]) != 4 or len(rep4["param_devices"]) != 4:
        fail("the pool or the params are not on all four devices")
    if len(set(rep4["pool_bytes_by_device"].values())) != 1:
        fail(f"uneven pool shards {rep4['pool_bytes_by_device']}")
    # heads mode replicates the weights and splits the pool evenly, so
    # every device holds the same bytes; more on device 0 means arrays
    # of the one-chip engine (or another stray copy) are still alive
    live = rep4["live_bytes"]
    print(f"[mesh] live array bytes per device with the mesh engine: "
          f"{live}")
    others = min(v for k, v in live.items() if k != 0)
    if live[0] > others * 1.02 + (64 << 10):
        fail(f"device 0 holds {live[0] - others} B more than the other "
             f"devices: {live}")
    if on_chip and rep4["decode_custom_calls"] == 0:
        fail("the mesh decode step holds no Pallas custom call")
    diff = [i for i, (a, b) in enumerate(zip(one, four))
            if not np.array_equal(a, b)]
    if diff:
        fail(f"heads-mode tokens differ from one chip in requests {diff}")
    print(f"[mesh] tokens of all {len(one)} requests equal to one chip")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh path (heads mode) and the "
                         "one-chip engine it is compared with")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU at smoke widths (phi3-mini's "
                         "equal query and KV head counts kept) with every "
                         "kernel in Pallas interpret mode; prints no "
                         "result line")
    args = ap.parse_args()

    import jax

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.cpu_rehearsal:
        fail(f"no TPU: JAX found platform {platform!r} "
             f"({len(jax.devices())} device(s))")
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {device_line(jax)}")
    clock = CompileClock()
    if args.cpu_rehearsal:
        import repro.kernels

        # every kernel op resolves its backend through this function:
        # the rehearsal takes the TPU's path through the Pallas interpreter
        repro.kernels.platform_backend = lambda: "interpret"
        model = dataclasses.replace(configs.smoke_config(ARCH),
                                    n_kv_heads=4)
        wl = REHEARSAL
    else:
        model, wl = configs.get_config(ARCH), FULL
    prompts = make_prompts(model.vocab, wl)
    print(f"requests: {len(prompts)} prompts of lengths "
          f"{[p.size for p in prompts]}, {wl.max_new} new tokens each, "
          f"greedy; slots {SLOTS}, max_len {wl.max_len}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(model, wl, prompts, clock, jax, not args.cpu_rehearsal)
    else:
        run_one_chip(model, wl, prompts, clock, jax, not args.cpu_rehearsal)
    print(f"total compile seconds {clock.seconds:.1f}, wall "
          f"{time.perf_counter() - t0:.1f} (one unrepeated run)")
    line = device_line(jax)
    if args.cpu_rehearsal:
        print(f"rehearsal passed on {line}; no result line off the chip")
        return
    print(json.dumps({"ok": True, "device": line}))


if __name__ == "__main__":
    main()
