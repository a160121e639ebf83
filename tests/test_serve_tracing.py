"""The serving engine's host spans (``repro.serve.tracing``) on the
profiler's clock: every span is emitted by a paged run that decodes,
prefills (monolithic and chunked) and verifies; spans nest where the
engine's layers do and carry their counters; named scopes leave the
compiled decode step unchanged; the iteration-wall window stays bounded."""

import dataclasses
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, ProfileOptions

from repro import configs
from repro.models import registry, schema as schema_lib
from repro.serve import api, tracing
from repro.serve.api import LLMEngine
from repro.serve.config import EngineConfig

BLK = 8


@pytest.fixture(scope="module")
def float_setup():
    cfg = dataclasses.replace(configs.smoke_config("phi3-mini-3.8b"),
                              serve_quant=False)
    arch = registry.build(cfg)
    params = schema_lib.init_params(arch.schema(), jax.random.key(0))
    return cfg, arch, params


def _engine(float_setup, **kw):
    cfg, arch, params = float_setup
    ec = EngineConfig(**{**dict(slots=2, max_len=64, backend="paged",
                                block_len=BLK), **kw})
    return LLMEngine(arch, params, ec)


def _prompt(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab, size=n).astype(np.int32)


def _spans(trace_dir):
    """Every program span of the trace: ``(name, start, end, stats)`` in
    order of start."""
    path = sorted(trace_dir.glob("**/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in tracing.NAMES:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def traced(float_setup, tmp_path_factory):
    """One profiled stretch: a monolithic engine with more requests than
    slots, a chunked one with a prompt longer than its chunk, and a
    speculative one with a repeating prompt. Each engine is warmed first,
    so the trace holds few compiles."""
    cfg = float_setup[0]
    mono = _engine(float_setup)
    chunked = _engine(float_setup, prefill_chunk_tokens=2 * BLK)
    spec = _engine(float_setup, spec_tokens=2)
    # a period-3 prompt on which this model's greedy answer repeats the
    # period, so the n-gram drafter proposes
    loop = np.tile(np.random.default_rng(1).integers(0, cfg.vocab, 3), 5)

    def work(eng, prompts):
        reqs = [eng.request(eng.add_request(p, max_new_tokens=8))
                for p in prompts]
        eng.run_until_drained()
        return reqs

    plan = [(mono, [_prompt(cfg, n, seed=n) for n in (5, 11, 17)]),
            (chunked, [_prompt(cfg, 37, seed=1), _prompt(cfg, 6, seed=2)]),
            (spec, [loop, loop])]
    for eng, prompts in plan:
        work(eng, prompts)
    drafted = spec.spec_drafted
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out), profiler_options=opts):
        reqs = [work(eng, prompts) for eng, prompts in plan]
    return _spans(out), reqs, spec.spec_drafted - drafted


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_every_span_is_emitted(traced):
    spans = traced[0]
    assert {s[0] for s in spans} == set(tracing.NAMES)


@pytest.mark.parametrize("child,parent", [
    (tracing.ENGINE_FETCH, tracing.ENGINE_STEP),
    (tracing.BACKEND_DECODE, tracing.ENGINE_STEP),
    (tracing.ENGINE_SCHEDULE, tracing.ENGINE_STEP),
    (tracing.ENGINE_FINISH, tracing.ENGINE_STEP),
    (tracing.BACKEND_VERIFY, tracing.ENGINE_STEP),
    (tracing.ENGINE_ADMIT, tracing.ENGINE_SCHEDULE),
    (tracing.BACKEND_PREFILL, tracing.ENGINE_ADMIT),
    (tracing.BACKEND_TABLES, tracing.BACKEND_DECODE),
])
def test_spans_nest(traced, child, parent):
    spans = traced[0]
    kids = [s for s in spans if s[0] == child]
    parents = [s for s in spans if s[0] == parent]
    assert kids
    if child == tracing.BACKEND_TABLES:
        # tables upload only when changed, and also before a verify
        parents += [s for s in spans if s[0] == tracing.BACKEND_VERIFY]
    for k in kids:
        assert any(_inside(k, p) for p in parents), (child, k)


def test_admission_spans_carry_the_request(traced):
    spans, reqs, _ = traced
    by_rid = {}
    for engine_reqs in reqs:
        for r in engine_reqs:
            by_rid.setdefault(r.rid, []).append(r)
    admits = [s for s in spans if s[0] == tracing.ENGINE_ADMIT]
    prefills = [s for s in spans if s[0] in (tracing.BACKEND_PREFILL,
                                             tracing.BACKEND_PREFILL_CHUNK)]
    assert len(admits) == sum(len(g) for g in reqs)
    for a in admits:
        st = a[3]
        assert st["rid"] in by_rid
        assert st["prompt_tokens"] in {len(r.prompt)
                                       for r in by_rid[st["rid"]]}
        assert st["waited_us"] >= 0
        inner = [p for p in prefills if _inside(p, a)]
        assert len(inner) == 1 and inner[0][3]["rid"] == st["rid"]
        assert inner[0][3]["width"] >= inner[0][3]["tokens"] > 0
    # the chunked prompt's later chunks continue outside engine.admit,
    # under the same rid
    chunks = [p for p in prefills if p[0] == tracing.BACKEND_PREFILL_CHUNK]
    assert len({p[3]["rid"] for p in chunks}) < len(chunks)


def test_admitted_at_between_submit_and_first_token(traced):
    reqs = traced[1]
    for engine_reqs in reqs:
        for r in engine_reqs:
            assert r.submitted_at <= r.admitted_at <= r.first_token_at


def test_step_counters(traced):
    """Each ``engine.step``'s decode rows are its dispatch's rows; the
    verify spans count the drafts the engine counted."""
    spans, _, drafted = traced
    steps = [s for s in spans if s[0] == tracing.ENGINE_STEP]
    assert [s[3]["iteration"] for s in steps[:2]] == [
        steps[0][3]["iteration"], steps[0][3]["iteration"] + 1]
    for st in steps:
        inner = [s for s in spans if _inside(s, st) and s is not st]
        dispatch = [s for s in inner if s[0] in (tracing.BACKEND_DECODE,
                                                 tracing.BACKEND_VERIFY)]
        assert [d[3]["rows"] for d in dispatch] == \
            ([st[3]["running"]] if st[3]["running"] else [])
        assert st[3]["written_tokens"] >= st[3]["running"]
        assert st[3]["reserved_blocks"] >= st[3]["running"]
    verify = [s for s in spans if s[0] == tracing.BACKEND_VERIFY]
    assert sum(v[3]["drafted"] for v in verify) == drafted > 0


def test_iteration_window_stays_bounded(float_setup, monkeypatch):
    """A long drain keeps only the last ``ITER_WINDOW`` iteration walls
    and token counts, paired entry by entry."""
    monkeypatch.setattr(api, "ITER_WINDOW", 5)
    eng = _engine(float_setup)
    cfg = float_setup[0]
    for n in (3, 4, 5):
        eng.add_request(_prompt(cfg, n, seed=n), max_new_tokens=8)
    eng.run_until_drained()
    assert eng.iterations > 5
    assert len(eng._iter_walls) == len(eng._iter_tokens) == 5
    m = eng.metrics()
    assert m["iter_wall_p99_ms"] >= m["iter_wall_p50_ms"] > 0


_METADATA = re.compile(r",?\s*metadata=\{[^}]*\}")
_NAME = re.compile(r"%[\w.\-]+")
_SOURCE_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames")


def _decode_hlo(float_setup):
    """The compiled decode step's text without operation metadata (and
    the source-location tables it points into), its instructions and
    computations renamed in order of first use (their numbering follows
    the source locations), and the named scopes its metadata holds."""
    eng = _engine(float_setup)
    samp = eng._greedy_sampling_vectors(eng.ec.slots)
    text = eng.lower_decode(samp).compile().as_text()
    scopes = {s for s in ("kv_write", "attention", "mlp", "unembed",
                          "sample") if f"/{s}/" in text}
    ops, table = [], False
    for line in text.splitlines():
        if line in _SOURCE_TABLES:
            table = True
        elif not line.strip():
            table = False
        elif not table:
            ops.append(_METADATA.sub("", line))
    names = {}
    text = _NAME.sub(lambda m: names.setdefault(m.group(), f"%{len(names)}"),
                     "\n".join(ops))
    return text, scopes


def test_named_scopes_change_metadata_only(float_setup, monkeypatch):
    """The compiled decode step with its named scopes and with every
    scope turned into a no-op differ only in operation metadata."""
    from jax._src import source_info_util

    scoped, scopes = _decode_hlo(float_setup)
    assert scopes == {"kv_write", "attention", "mlp", "unembed", "sample"}
    cm = source_info_util.ExtendNameStackContextManager
    monkeypatch.setattr(cm, "__enter__", lambda self: None)
    monkeypatch.setattr(cm, "__exit__", lambda self, *exc: None)
    plain, none = _decode_hlo(float_setup)
    assert not none
    assert plain == scoped


def test_traced_parts_stay_under_the_one_fetch_contract():
    """The parts of an iteration that spans now enclose are each marked
    hot, so the host-sync rule checks them, and the analysis finds
    nothing in the serving modules beyond the checked-in baseline."""
    from pathlib import Path

    from repro.analysis import HOT_PATH_ATTR, load_baseline, run_paths
    from repro.serve.backends import PagedBackend

    for fn in (LLMEngine._step, LLMEngine._iterate, LLMEngine._schedule,
               LLMEngine._fetch_and_finish, LLMEngine._commit_tokens,
               PagedBackend.begin_iteration, PagedBackend._prepare_rows,
               PagedBackend.decode, PagedBackend.verify):
        assert getattr(fn, HOT_PATH_ATTR, False), fn.__name__
    repo = Path(__file__).resolve().parent.parent
    findings, _, errors = run_paths([str(repo / "src/repro/serve")])
    assert not errors, [e.render() for e in errors]
    baseline = {(Path(b.file).name, b.rule) for b in
                load_baseline(str(repo / "analysis_baseline.json"))}
    assert {(Path(f.file).name, f.rule) for f in findings} <= baseline
