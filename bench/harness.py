"""One run of one cell: build the engine, warm it, drive the open loop,
reduce what was seen to metrics, and check the served tokens.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by name (``configs/``, ``traffic/``,
``metrics/``); this module is the general part. From the program it
takes only the system under test (``LLMEngine`` on the paged backend)
and its counters; the weights, the traffic, the clock, the trace
reduction, the operation counts and the reference are the benchmark's.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import sys
import time
from typing import Dict, List, Optional

import numpy as np

import loadgen

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_SECONDS = 20.0     # the traced stretch: the last seconds of the window


class BenchError(RuntimeError):
    """A cell that cannot be run as asked (bad name, no chip, no room)."""


# -- finding a cell's files ------------------------------------------------


def load_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


def cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The workload entry ``name`` of ``BENCHMARK.json`` with its
    configuration file, traffic file and metric entries resolved."""
    spec = load_json(root / "BENCHMARK.json")
    wl = {w["name"]: w for w in spec["workloads"]}.get(name)
    if wl is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    traffic = load_json(root / "bench" / "traffic" / f"{wl['traffic']}.json")

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return dict(
        workload=wl, spec=spec,
        config=load_json(root / cfg_entry["file"]),
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """``bench/metrics/<name>.py``'s ``read(run)``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no reader {path} for per-layer metric {name!r}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# -- the configuration as the program runs it --------------------------------


def model_sizes(config: dict) -> dict:
    """The sizes the benchmark's weights, counts and reference use, from
    the published keys of the configuration file."""
    heads = config["num_attention_heads"]
    return dict(
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // heads,
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]))


def rehearsal_cell(c: dict) -> dict:
    """The same cell at sizes a CPU can run: two layers, narrow widths
    (the query/KV head ratio kept), short prompts and answers. It proves
    control flow only."""
    c = json.loads(json.dumps(c))
    cfg, tr = c["config"], c["traffic"]
    ratio = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    cfg.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=max(1, 4 // ratio), intermediate_size=128,
               vocab_size=512)
    blk = cfg["engine"]["block_len"]
    cfg["engine"] = dict(cfg["engine"], slots=4, num_blocks=None,
                         max_len=max(96, 2 * blk) // blk * blk)
    tr.update(warmup_s=0.5, drain_s=60.0, max_new_tokens=16, rate_rps=4.0,
              catalog=dict(tr["catalog"], requests=32), check_tokens=64)
    tr["prompt_tokens"] = dict(tr["prompt_tokens"], median=20, min=4, max=48)
    tr["output_tokens"] = dict(tr["output_tokens"], median=6, min=2, max=16)
    return c


def build_engine(c: dict, params, *, serve_quant: Optional[bool] = None):
    """``LLMEngine`` on the paged backend as the configuration file says."""
    from repro.models import registry
    from repro.models.config import ModelConfig
    from repro.serve import EngineConfig, LLMEngine

    cfg = c["config"]
    m = model_sizes(cfg)
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]:
        raise BenchError("the dense reference covers SwiGLU with an "
                         "untied head only")
    quant = cfg["serve"]["serve_quant"] if serve_quant is None \
        else serve_quant
    model = ModelConfig(
        name=cfg["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], d_ff=m["d_ff"], vocab=m["vocab"],
        act="swiglu", rope_theta=m["rope_theta"], tie_embeddings=False,
        dtype=cfg["serve"]["dtype"], serve_quant=quant,
        max_seq=cfg["engine"]["max_len"])
    e = cfg["engine"]
    ec = EngineConfig(backend="paged", scheduler=e["scheduler"],
                      slots=e["slots"], max_len=e["max_len"],
                      block_len=e["block_len"], num_blocks=e["num_blocks"],
                      prefill_chunk_tokens=e["prefill_chunk_tokens"],
                      admit_batch=e["admit_batch"])
    return LLMEngine(registry.build(model), params, ec)


# -- the compile clock -----------------------------------------------------


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, with the time
    of each event, so compiles inside the window can be counted."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.at: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs
            self.at.append(time.perf_counter())

    def count(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.at if t0 <= t < t1)


# -- the open loop -----------------------------------------------------------


@dataclasses.dataclass
class Req:
    due: float                 # absolute, on the benchmark's clock
    prompt: np.ndarray
    target: int                # tokens the client reads
    submit: Optional[float] = None
    admit: Optional[float] = None    # start of the step that admitted it
    tokens: List[float] = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    handle: Optional[int] = None
    output: Optional[np.ndarray] = None


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    decode_rows: int           # requests that decoded in this step
    lens: List[int]            # their sequence lengths before the step
    prefill_tokens: List[int]  # prompt lengths admitted in this step
    reserved_blocks: int       # KV pool blocks admitted requests hold or
                               # have reserved, after the step


def allocators(engine) -> list:
    """The paged backend's block allocators (one per device that owns a
    slice of the pool)."""
    b = engine.backend
    return b.allocs or [b.alloc]


def reserved_blocks(engine) -> int:
    """Blocks no new request can take: held by admitted requests or
    reserved for their worst case (prompt + ``max_new_tokens``)."""
    return sum(a.layout.num_blocks - 1 - a.available_blocks
               for a in allocators(engine))


def drive(engine, reqs: List[Req], *, until, deadline, max_new: int,
          on_step=None) -> List[Step]:
    """Run the open loop: submit each request when it is due, step the
    engine, stop a request once its client has read ``target`` tokens (as
    an end-of-sequence token would). Returns when every request that
    ``until`` names is done, or at ``deadline``."""
    from jax.profiler import TraceAnnotation

    from repro.serve.request import RequestState

    clock = time.perf_counter
    order = sorted(range(len(reqs)), key=lambda i: reqs[i].due)
    nxt = 0
    live: Dict[int, int] = {}         # handle -> request index
    steps: List[Step] = []
    waiting = (RequestState.WAITING, RequestState.PREEMPTED)
    while True:
        now = clock()
        while nxt < len(order) and reqs[order[nxt]].due <= now:
            r = reqs[order[nxt]]
            r.handle = engine.add_request(r.prompt, max_new_tokens=max_new)
            r.submit = clock()
            live[r.handle] = order[nxt]
            nxt += 1
        if all(reqs[i].done is not None for i in until) or now > deadline:
            for h in live:     # what is still in flight leaves the engine
                engine.abort(h)
            return steps
        if not live:
            if nxt < len(order):
                with TraceAnnotation("bench.wait_arrival"):
                    time.sleep(max(0.0, reqs[order[nxt]].due - clock()))
            continue
        rows = [(h, engine.request(h)) for h in live]
        running = [len(q.prompt) + len(q.output) for _, q in rows
                   if q.state == RequestState.RUNNING]
        t0 = clock()
        with TraceAnnotation("bench.step"):
            outs = engine.step()
        t1 = clock()
        prefill = []
        for o in outs:
            if o.token is not None:
                reqs[live[o.rid]].tokens.append(t1)
        for h, q in rows:
            r = reqs[live[h]]
            if r.admit is None and q.state not in waiting:
                r.admit = t0
                prefill.append(len(r.prompt))
            if not q.finished and len(q.output) >= r.target:
                engine.abort(h)
            if q.finished:
                r.done = t1
                r.output = np.asarray(q.output, np.int32)
                del live[h]
        steps.append(Step(t0, t1, len(running), running, prefill,
                          reserved_blocks(engine)))
        if on_step is not None:
            on_step(t1)


def warm(engine, lengths: List[int], max_new: int, vocab: int) -> None:
    """Run every prompt length of ``lengths`` through prefill and a decode
    step, so each program the run will use is compiled (or read back from
    the persistent cache) before the traffic starts."""
    rng = np.random.default_rng(0)
    reqs = [Req(due=0.0, prompt=rng.integers(0, vocab, n, dtype=np.int32),
                target=2) for n in sorted(set(lengths))]
    drive(engine, reqs, until=range(len(reqs)), deadline=math.inf,
          max_new=max_new)


# -- statistics ------------------------------------------------------------


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (linear between order statistics)."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclasses.dataclass
class Run:
    """What one run saw; the metric readers read it."""
    c: dict
    sizes: dict
    reqs: List[Req]
    window: List[int]          # indices of the requests due in the window
    steps: List[Step]
    win: tuple                 # (start, end) on the benchmark's clock
    drain_deadline: float
    counters: dict             # pool size, compiles in the window
    peaks: dict
    t0: float = 0.0            # when the traffic started
    tracer: Optional["Tracer"] = None
    trace: Optional[dict] = None   # the trace reduction, when traced

    @property
    def seconds(self) -> float:
        return self.win[1] - self.win[0]

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if self.win[0] <= s.t1 < self.win[1]]

    def ttft_ms(self) -> List[float]:
        """Due -> first token of every request due in the window; one
        that never answered counts at the drain deadline (a lower bound,
        and a miss of any latency limit)."""
        out = []
        for i in self.window:
            r = self.reqs[i]
            first = r.tokens[0] if r.tokens else self.drain_deadline
            out.append((first - r.due) * 1e3)
        return out

    def itl_ms(self) -> List[float]:
        return [(b - a) * 1e3 for i in self.window
                for a, b in zip(self.reqs[i].tokens, self.reqs[i].tokens[1:])]

    def tokens_in_window(self) -> int:
        lo, hi = self.win
        return sum(1 for r in self.reqs for t in r.tokens if lo <= t < hi)


def end_to_end(run: Run, setup_s: float) -> Dict[str, float]:
    return {
        "ttft_p50_ms": percentile(run.ttft_ms(), 50),
        "itl_p90_ms": percentile(run.itl_ms(), 90),
        "itl_p99_ms": percentile(run.itl_ms(), 99),
        "tokens_per_s": run.tokens_in_window() / run.seconds,
        "setup_s": setup_s,
    }


# -- the check -------------------------------------------------------------


def check_sample(run: Run, seed: int) -> List[int]:
    """Finished requests of the window to compare with the reference,
    drawn from the seed: the one with the most served tokens and the one
    with the longest prompt, then others until ``check_tokens`` served
    tokens are in the sample."""
    done = [i for i in run.window if run.reqs[i].output is not None
            and len(run.reqs[i].output)]
    if not done:
        return []
    pick = [max(done, key=lambda i: len(run.reqs[i].output)),
            max(done, key=lambda i: len(run.reqs[i].prompt))]
    rng = np.random.default_rng(loadgen.seed_sequence(seed, 6))
    for i in rng.permutation(done).tolist():
        if sum(len(run.reqs[j].output) for j in set(pick)) \
                >= run.c["traffic"]["check_tokens"]:
            break
        pick.append(i)
    return sorted(set(pick))


def reference_gaps(params, sizes: dict, reqs: List[Req]) -> np.ndarray:
    """Per served token: the float32 reference's best logit minus its
    logit for the served token, teacher-forced on prompt + served
    tokens."""
    sys.path.insert(0, str(BENCH / "references"))
    import dense

    gaps = []
    for r in reqs:
        seq = np.concatenate([r.prompt, r.output[:-1]])
        pos = np.arange(len(r.prompt) - 1, len(seq))
        lg = np.asarray(dense.logits_at(params, seq, pos, sizes))
        gaps.append(lg.max(-1) - lg[np.arange(len(pos)), r.output])
    return np.concatenate(gaps) if gaps else np.zeros((0,))


def checks(run: Run, params, seed: int) -> dict:
    """The compared numbers, each with its limit (``config["check"]``):
    requests of the window whose answer is not as long as their client
    read, and the widest reference gap of a served token in the sample
    (infinite when no request of the window finished)."""
    limits = run.c["config"]["check"]
    done = [run.reqs[i] for i in run.window if run.reqs[i].output is not None]
    wrong_len = sum(1 for r in done if len(r.output) != r.target)
    sample = [run.reqs[i] for i in check_sample(run, seed)]
    gaps = reference_gaps(params, run.sizes, sample)
    worst = float(gaps.max()) if gaps.size else math.inf
    return {
        "wrong_lengths": {"value": wrong_len, "limit": 0},
        "worst_gap": {"value": worst, "limit": limits["worst_gap"]},
    }


def passed(ch: dict) -> bool:
    return all(ch[k]["value"] <= ch[k]["limit"] for k in ch)


# -- one run -----------------------------------------------------------------


def device_info(jax) -> dict:
    """The device as JAX reports it; the peak is the fullest chip's."""
    d = jax.devices()[0]
    peak = max((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dev in jax.devices())
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(peak)}


@dataclasses.dataclass
class Setup:
    """A built and warmed engine with the weights it serves."""
    c: dict
    sizes: dict
    params: dict
    engine: object
    pool: dict
    clock: CompileClock


def prepare(c: dict, seed: int, *, serve_quant: Optional[bool] = None,
            lengths: Optional[List[int]] = None) -> Setup:
    """Set-up: the weights from the seed, the engine as the configuration
    file says, and every program shape that prompts of ``lengths`` (by
    default every length of the cell's catalog) reach."""
    import weights

    clock = CompileClock()
    sizes = model_sizes(c["config"])
    tr = c["traffic"]
    if tr["prompt_tokens"]["max"] + tr["max_new_tokens"] \
            > c["config"]["engine"]["max_len"]:
        raise BenchError("prompt clip + max_new_tokens exceeds max_len")
    params = weights.make(sizes, seed)
    engine = build_engine(c, params, serve_quant=serve_quant)
    pool = dict(getattr(engine.backend, "pool_sizing", {}) or {})
    pool.setdefault("num_blocks", engine.backend.layout.num_blocks)
    if lengths is None:
        lengths = [p for p, _ in loadgen.catalog(tr)]
    warm(engine, lengths, tr["max_new_tokens"], sizes["vocab"])
    return Setup(c, sizes, params, engine, pool, clock)


def open_loop(s: Setup, traffic: dict, seed: int, seconds: float,
              tracer_for=None) -> Run:
    """The mix's arrivals from ``seed``: warm-up stretch, ``seconds`` of
    window, drain. ``tracer_for(win)`` may give a ``Tracer``."""
    engine = s.engine
    arrivals = loadgen.schedule(traffic, seed, seconds)
    prompts = loadgen.prompts(arrivals, s.sizes["vocab"], seed)
    t0 = time.perf_counter()
    reqs = [Req(due=t0 + a.due_s, prompt=p, target=a.output_len)
            for a, p in zip(arrivals, prompts)]
    win = (t0 + traffic["warmup_s"], t0 + traffic["warmup_s"] + seconds)
    window = [i for i, r in enumerate(reqs) if win[0] <= r.due < win[1]]
    deadline = win[1] + traffic["drain_s"]
    tracer = tracer_for(win) if tracer_for else None
    steps = drive(engine, reqs, until=window, deadline=deadline,
                  max_new=traffic["max_new_tokens"],
                  on_step=tracer.on_step if tracer else None)
    if tracer is not None:
        tracer.stop()
    counters = dict(pool_blocks=s.pool["num_blocks"],
                    pool_usable_blocks=sum(a.layout.num_blocks - 1
                                           for a in allocators(engine)),
                    block_len=s.c["config"]["engine"]["block_len"],
                    compiles_in_window=s.clock.count(*win))
    return Run(c=s.c, sizes=s.sizes, reqs=reqs, window=window, steps=steps,
               win=win, drain_deadline=deadline, counters=counters,
               peaks={}, t0=t0, tracer=tracer)


def run_cell(c: dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, trace_dir: Optional[pathlib.Path] = None,
             serve_quant: Optional[bool] = None,
             log=lambda s: print(s, file=sys.stderr)) -> dict:
    """One run; returns the result line's fields (``metrics`` holds every
    number, end-to-end and per-layer, under its name)."""
    import jax

    from peaks import peaks_for

    lengths = [a.prompt_len
               for a in loadgen.schedule(c["traffic"], seed, seconds)]
    s = prepare(c, seed, serve_quant=serve_quant, lengths=lengths)
    tracer_for = None
    if trace:
        def tracer_for(win):
            return Tracer(trace_dir, win[1] - min(TRACE_SECONDS, seconds),
                          win[1])
    run = open_loop(s, c["traffic"], seed, seconds, tracer_for)
    # set-up ends where the traffic starts
    setup_s = run.t0 - t_start
    dev = device_info(jax)
    params, pool, compile_s = s.params, s.pool, s.clock.seconds
    del s
    gc.collect()
    if dev["platform"] == "tpu":
        run.peaks = peaks_for(dev["kind"])
    if run.tracer is not None:
        run.trace = run.tracer.reduce(run)
    metrics = end_to_end(run, setup_s)
    for m in c["per_layer"]:
        metrics[m["name"]] = metric_reader(m["name"])(run)
    log(f"setup {setup_s:.2f} s, compile seconds {compile_s:.2f}, "
        f"compiles in window {run.counters['compiles_in_window']}, "
        f"pool {pool}")
    failed = sum(1 for i in run.window if run.reqs[i].done is None)
    ch = checks(run, params, seed)
    return dict(correct=passed(ch), attempted=len(run.window),
                failed=failed, metrics=metrics, device=dev, checks=ch,
                run=run)


class Tracer:
    """The profiler over the last ``TRACE_SECONDS`` of the window, with the
    benchmark's host spans on the same clock. The profiler is stopped on a
    thread of its own, so writing the trace out does not stall the open
    loop that keeps running through the drain."""

    def __init__(self, out_dir, start: float, stop: float):
        self.dir, self.t_start, self.t_stop = out_dir, start, stop
        self.span = None          # [start, stop] actually traced
        self.start_s = None       # how long start_trace held the loop
        self._stopper = None

    def on_step(self, now: float) -> None:
        import threading

        import jax

        if self.span is None and now >= self.t_start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # keep the host's cost down
            t0 = time.perf_counter()
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.span = [time.perf_counter(), None]
            self.start_s = self.span[0] - t0
        elif self.span is not None and self.span[1] is None \
                and now >= self.t_stop:
            self.span[1] = time.perf_counter()
            self._stopper = threading.Thread(target=jax.profiler.stop_trace)
            self._stopper.start()

    def stop(self) -> None:
        """Make sure the profiler has stopped and its file is written."""
        import jax

        if self.span is not None and self.span[1] is None:
            self.span[1] = time.perf_counter()
            jax.profiler.stop_trace()
        if self._stopper is not None:
            self._stopper.join()

    def reduce(self, run: Run) -> Optional[dict]:
        import trace as trace_lib

        if self.span is None:
            return None
        out = trace_lib.reduce_dir(self.dir, run, tuple(self.span))
        out["start_trace_s"] = self.start_s
        return out
