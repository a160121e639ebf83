"""The program-span reduction (``bench/spans.py``): idle attribution by
overlap with the innermost program span, the sum to the device's idle
share, the engine's host time, and the readers, checked by hand on a tiny
event set and on a few steps recorded on a TPU v5e (``data/``); and, on a
traced CPU rehearsal, the engine's own counters against the benchmark's
outside counts."""

import json
import pathlib

import pytest

import harness
import spans as spans_lib
import trace as trace_lib

DATA = pathlib.Path(__file__).resolve().parent / "data"

NAMES = {"op_lines": ["XLA Ops"], "module_lines": ["XLA Modules"],
         "programs": {"decode": ["jit__dec"], "prefill": ["jit__pre"]},
         "kernels": {"paged_attention": ["paged_attention"]}}

# times in ns, a stretch of 1000: the device runs 0-100, 300-700 and
# 800-900. The first step's host work covers the idle 100-300: its own
# code 100-150, 250-260 and 280-300, the fetch 150-250, the finish
# 260-280. The second step runs while the device is busy. Idle 700-800
# falls under no program span; 900-1000 is the stretch's edge.
TINY = {
    "/device:TPU:0": {
        "XLA Modules": [["jit__dec(1)", 0, 100], ["jit__dec(2)", 300, 400]],
        "XLA Ops": [["fusion.1", 0, 60], ["fusion.2", 40, 60],
                    ["fusion.3", 300, 400], ["fusion.4", 800, 100]],
    },
    "spans": [
        ["engine.step", 50, 300, {"iteration": 1, "running": 2}],
        ["backend.decode", 60, 20, {"rows": 2}],
        ["engine.fetch", 150, 100, {"arrays": 1}],
        ["engine.finish", 260, 20, {"finished": 0, "tokens": 2}],
        ["engine.step", 600, 50, {"iteration": 2, "running": 2}],
        ["engine.fetch", 610, 10, {"arrays": 1}],
    ],
}


def test_tiny_reduction_by_hand():
    r = spans_lib.reduce_spans(TINY, 1000e-9, NAMES)
    assert r["steps"] == 2
    assert r["busy_s"] == pytest.approx(600e-9)
    assert r["gap_idle_s"] == pytest.approx(300e-9)     # 100-300, 700-800
    assert r["idle_in_fetch_s"] == pytest.approx(100e-9)
    assert r["idle_in_engine_s"] == pytest.approx(100e-9)
    # 700-800 outside every step, and the edge 900-1000
    assert r["idle_rest_s"] == pytest.approx(200e-9)
    # innermost span over each idle instant: the step's own code 80 ns,
    # the fetch 100, the finish 20
    assert r["idle_by_span_s"] == pytest.approx(
        {"engine.step": 80e-9, "engine.fetch": 100e-9,
         "engine.finish": 20e-9})
    # (300 - 100) and (50 - 10) ns of host outside the fetch, in ms
    assert r["engine_host_ms"] == pytest.approx(120e-6)


def test_idle_parts_sum_to_the_idle_share():
    r = spans_lib.reduce_spans(TINY, 1000e-9, NAMES)
    t = trace_lib.reduce_events(TINY, 1000e-9, NAMES)
    parts = r["idle_in_fetch_s"] + r["idle_in_engine_s"] + r["idle_rest_s"]
    assert 100 * parts / r["window_s"] == pytest.approx(
        100 * (1 - t["busy_s"] / t["window_s"]), abs=1e-9)


def test_timeline_names_the_open_spans_innermost_last():
    segs = spans_lib.timeline([["a", 0, 100, {}], ["b", 10, 20, {}],
                               ["c", 10, 5, {}], ["d", 50, 10, {}],
                               ["e", 200, 10, {}]])
    assert segs == [(0, 10, ("a",)), (10, 15, ("a", "b", "c")),
                    (15, 30, ("a", "b")), (30, 50, ("a",)),
                    (50, 60, ("a", "d")), (60, 100, ("a",)),
                    (200, 210, ("e",))]


def test_overlap_sums_by_label():
    gaps = [(0, 10), (20, 40), (50, 55)]
    segs = [(5, 25, ("x",)), (30, 60, ("y",))]
    assert spans_lib.overlap(gaps, segs) == {("x",): 10, ("y",): 15}


def test_program_without_spans_reads_nothing():
    old = {k: v for k, v in TINY.items() if k != "spans"}
    assert spans_lib.reduce_spans(old, 1000e-9, NAMES)["steps"] == 0


class _Run:
    def __init__(self, trace):
        self.trace = trace
        self.tracer = type("T", (), {"dir": pathlib.Path("unused")})()


@pytest.mark.parametrize("events,expected", [
    (TINY, {"idle_in_fetch_share": 10.0, "idle_in_engine_share": 10.0,
            "engine_host_ms": 120e-6}),
    ({k: v for k, v in TINY.items() if k != "spans"},
     {"idle_in_fetch_share": None, "idle_in_engine_share": None,
      "engine_host_ms": None}),
])
def test_readers_parse_the_trace_once(monkeypatch, events, expected):
    loads = []
    monkeypatch.setattr(trace_lib, "find_xplane", lambda d: d)
    monkeypatch.setattr(spans_lib, "load_spans",
                        lambda path: loads.append(path) or events)
    monkeypatch.setattr(trace_lib, "names", lambda: NAMES)
    run = _Run({"busy_s": 600e-9, "window_s": 1000e-9})
    for name, want in expected.items():
        got = harness.metric_reader(name)(run)
        assert got == (want if want is None else pytest.approx(want))
    assert len(loads) == 1


@pytest.mark.parametrize("trace", [None, {"busy_s": None, "window_s": 1.0}])
def test_untraced_run_reads_nothing(trace):
    for name in ("idle_in_fetch_share", "idle_in_engine_share",
                 "engine_host_ms"):
        assert harness.metric_reader(name)(_Run(trace)) is None


def _recorded():
    return json.loads((DATA / "v5e_chat_spans.json").read_text())


def test_recorded_v5e_steps():
    """A few decode steps of a traced phi3-mini.chat window on a TPU v5e,
    with the engine's spans; the numbers expected were worked out when
    it was recorded (``expected`` in the file)."""
    rec = _recorded()
    r = spans_lib.reduce_spans(rec["events"], rec["window_s"])
    for k, v in rec["expected"].items():
        assert r[k] == pytest.approx(v, rel=1e-9), k
    t = trace_lib.reduce_events(rec["events"], rec["window_s"])
    parts = r["idle_in_fetch_s"] + r["idle_in_engine_s"] + r["idle_rest_s"]
    assert 100 * parts / r["window_s"] == pytest.approx(
        100 * (1 - t["busy_s"] / t["window_s"]), abs=0.01)


def test_recorded_v5e_clocks_differ_by_one_offset():
    """On the recorded steps the device's times read early against the
    host's spans: read raw, a decode program would start before the
    ``backend.decode`` span that dispatched it opened. One constant shift
    puts every program after its dispatch opened and before the
    ``engine.fetch`` of the same step, which waits on its tokens, closed."""
    rec = _recorded()
    ev = rec["events"]
    mods = sorted(ev["/device:TPU:0"]["XLA Modules"], key=lambda e: e[1])
    sp = ev["spans"]
    lo, hi = spans_lib.clock_offset(mods, sp, ["jit__dec"])
    assert 0 < lo <= hi
    shift = (lo + hi) / 2
    steps = sorted((e for e in sp if e[0] == "engine.step"),
                   key=lambda e: e[1])
    assert len(mods) == len(steps) == 3

    def inside(name, step):
        s, d = step[1], step[2]
        found = [e for e in sp if e[0] == name and s <= e[1]
                 and e[1] + e[2] <= s + d]
        assert len(found) == 1
        return found[0]

    for (_, ms, md), step in zip(mods, steps):
        dec, fetch = inside("backend.decode", step), inside("engine.fetch",
                                                            step)
        assert ms < dec[1]                   # raw: before its dispatch
        assert dec[1] <= ms + shift
        assert ms + md + shift <= fetch[1] + fetch[2]


# the host's clock: two steps, each a decode dispatch and a fetch; the
# device's programs run exactly from each dispatch's start to its fetch's
# end, and the device's times read 5 ns early
SKEWED = {
    "/device:TPU:0": {
        "XLA Modules": [["jit__dec(1)", 5, 180], ["jit__dec(2)", 215, 170]],
        "XLA Ops": [["fusion.1", 5, 180], ["fusion.2", 215, 170]],
    },
    "spans": [
        ["engine.step", 0, 200, {}], ["backend.decode", 10, 20, {}],
        ["engine.fetch", 40, 150, {}], ["engine.finish", 190, 8, {}],
        ["engine.step", 210, 190, {}], ["backend.decode", 220, 20, {}],
        ["engine.fetch", 250, 140, {}],
    ],
}


def test_device_clock_is_aligned_by_causality():
    r = spans_lib.reduce_spans(SKEWED, 400e-9, NAMES)
    (lo, hi), shift = r["clock_ms"][0]
    assert (lo, hi, shift) == pytest.approx((5e-6, 5e-6, 5e-6))
    # idle 190-220 on the host's clock: the finish and the step's own
    # code 190-200, between the steps 200-210, the next step's code
    # 210-220; none in a fetch
    assert r["idle_in_fetch_s"] == pytest.approx(0)
    assert r["idle_in_engine_s"] == pytest.approx(20e-9)
    # 10 between the steps, and the edges: 0-10 and 390-400
    assert r["idle_rest_s"] == pytest.approx(30e-9)


def test_unpaired_programs_leave_the_clock_alone():
    # TINY's programs overlap no dispatch-to-fetch window by half
    assert spans_lib.reduce_spans(TINY, 1000e-9, NAMES)["clock_ms"] == \
        [[None, 0.0]]


# -- the engine's counters against the benchmark's outside counts -----------


def test_rehearsal_counters_agree_with_the_step_records(tmp_path,
                                                        monkeypatch):
    """One traced CPU rehearsal run: each ``backend.decode`` span's rows
    are the decode rows the benchmark counted for that step, each
    ``engine.step``'s written tokens the sum of the step's lengths, and
    its reserved blocks what the allocators held after the step before
    (nothing reserves blocks between two steps)."""
    import repro.kernels

    monkeypatch.setattr(repro.kernels, "platform_backend",
                        lambda: "interpret")
    c = harness.rehearsal_cell(harness.cell("phi3-mini.chat"))
    seed, seconds = 2**31 + 11, 2.0
    lengths = [a.prompt_len for a in harness.loadgen.schedule(
        c["traffic"], seed, seconds)]
    s = harness.prepare(c, seed, lengths=lengths)
    first = s.engine.iterations + 1
    run = harness.open_loop(
        s, c["traffic"], seed, seconds,
        lambda win: harness.Tracer(tmp_path, win[0], win[1]))
    ev = spans_lib.load_spans(trace_lib.find_xplane(tmp_path))["spans"]
    steps = [e for e in ev if e[0] == "engine.step"]
    decodes = [e for e in ev if e[0] == "backend.decode"]
    assert len(steps) >= 3
    checked = 0
    for st in steps:
        rec = run.steps[st[3]["iteration"] - first]
        assert st[3]["written_tokens"] == sum(rec.lens)
        assert st[3]["running"] == rec.decode_rows
        inner = [d for d in decodes
                 if st[1] <= d[1] and d[1] + d[2] <= st[1] + st[2]]
        assert [d[3]["rows"] for d in inner] == \
            ([rec.decode_rows] if rec.decode_rows else [])
        prev = st[3]["iteration"] - first - 1
        if prev >= 0:
            assert st[3]["reserved_blocks"] == run.steps[prev].reserved_blocks
            checked += 1
    assert checked >= 2
