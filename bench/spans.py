"""Program spans on the profiler's clock: what the engine's host thread was
doing while the device sat idle, and how long its own host work took.

The serving engine marks its layers with host spans (``engine.*`` and
``backend.*``, listed in ``repro.serve.tracing``). The profiler writes them
into the same ``.xplane.pb`` as the device's operations, on the same clock,
with their counters as event stats. Two steps, kept apart so the second is
tested on small event sets (``tests/bench``):

1. ``load_spans``: the device planes' operation and module lines (plain
   events ``[name, start_ns, duration_ns]``, as ``trace.load_xplane`` keeps
   them) and the program's spans with their stats, ``[name, start_ns,
   duration_ns, {stat: value}]``, under ``"spans"``.
2. ``reduce_spans``: each device-idle interval between operations is cut
   where program spans open and close, and each piece is put down to the
   innermost span covering it. The idle inside ``engine.fetch``, the idle
   in the rest of ``engine.step``, and the remainder (idle outside every
   ``engine.step``, and before the first and after the last operation of
   the traced stretch) add up to the idle time ``device_idle_share``
   counts. The engine's host time per iteration is each ``engine.step``
   less its ``engine.fetch``.

The profiler puts the device's events on the host's clock only to within
a fixed offset (on a TPU v5e they read 1-2 ms early against the host's
spans). ``clock_offset`` bounds it from causality: every decode program
starts after the ``backend.decode`` span that dispatched it opened, and
ends before the ``engine.fetch`` of the same step, which waits on its
tokens, closes. The reduction moves the device's events by the middle of
that range before it attributes idle time.

A program older than these spans gives a reduction with no steps, and the
readers read nothing. Busy intervals come from ``trace`` unchanged: the
union of each device plane's operation intervals, averaged over planes.
"""

from __future__ import annotations

import bisect
import math
import pathlib
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import trace as trace_lib

PROGRAM = re.compile(r"^(engine|backend)\.")
STEP = "engine.step"
FETCH = "engine.fetch"
DECODE = "backend.decode"


def load_spans(path: pathlib.Path, names_: Optional[dict] = None) -> dict:
    """The device planes' operation and module lines, and every program
    span of the host planes with its stats."""
    from jax.profiler import ProfileData

    nm = names_ or trace_lib.names()
    keep = set(nm["op_lines"]) | set(nm["module_lines"])
    pd = ProfileData.from_file(str(path))
    out: Dict[str, object] = {}
    spans: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            out[plane.name] = {
                line.name: [[e.name, e.start_ns, e.duration_ns]
                            for e in line.events]
                for line in plane.lines if line.name in keep}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if PROGRAM.match(e.name):
                        spans.append([e.name, e.start_ns, e.duration_ns,
                                      dict(e.stats)])
    out["spans"] = sorted(spans, key=lambda e: e[1])
    return out


def timeline(spans: List[list]) -> List[Tuple[float, float, tuple]]:
    """``(start, end, open)`` over each stretch in which a program span is
    open, ``open`` naming the spans open there from the outermost to the
    innermost (the one that opened last; of two that opened together,
    the shorter)."""
    bounds = sorted({t for _, s, d, *_ in spans for t in (s, s + d)})
    by_start = sorted(spans, key=lambda e: (e[1], -e[2]))
    out: List[Tuple[float, float, tuple]] = []
    live: List[list] = []
    k = 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(by_start) and by_start[k][1] <= a:
            live.append(by_start[k])
            k += 1
        live = [e for e in live if e[1] + e[2] > a]
        if live:
            out.append((a, b, tuple(e[0] for e in live)))
    return out


def overlap(gaps: List[Tuple[float, float]],
            segs: List[Tuple[float, float, tuple]]) -> Dict[tuple, float]:
    """Time each label of ``segs`` shares with ``gaps``; both sorted by
    start, neither overlapping itself."""
    out: Dict[tuple, float] = defaultdict(float)
    i = j = 0
    while i < len(gaps) and j < len(segs):
        a = max(gaps[i][0], segs[j][0])
        b = min(gaps[i][1], segs[j][1])
        if b > a:
            out[segs[j][2]] += b - a
        if gaps[i][1] < segs[j][1]:
            i += 1
        else:
            j += 1
    return dict(out)


def clock_offset(modules: List[list], spans: List[list],
                 patterns) -> Optional[Tuple[float, float]]:
    """``(lo, hi)``: the shifts (ns, added to the device's times) that put
    each decode program (module events matching ``patterns``) inside its
    step's window, from the ``backend.decode`` span's start to the
    ``engine.fetch`` span's end; a program is paired with the window it
    overlaps most, and one overlapping no window by half its length is
    left out. None when no program pairs."""
    def first_within(times, a, b):
        i = bisect.bisect_left(times, a)
        return times[i] if i < len(times) and times[i] <= b else None

    opens = sorted(s for n, s, *_ in spans if n == DECODE)
    closes = sorted(s + d for n, s, d, *_ in spans if n == FETCH)
    windows = []
    for n, a, d, *_ in sorted(spans, key=lambda e: e[1]):
        if n == STEP:
            o = first_within(opens, a, a + d)
            c = first_within(closes, a, a + d)
            if o is not None and c is not None:
                windows.append((o, c))
    starts = [w[0] for w in windows]
    lo, hi, paired = -math.inf, math.inf, 0
    for name, s, d in modules:
        if not trace_lib._matches(name, patterns):
            continue
        k = bisect.bisect_right(starts, s)
        best = max(windows[max(k - 1, 0):k + 1], default=None,
                   key=lambda w: min(w[1], s + d) - max(w[0], s))
        if best is None or min(best[1], s + d) - max(best[0], s) < d / 2:
            continue
        lo, hi = max(lo, best[0] - s), min(hi, best[1] - s - d)
        paired += 1
    return (lo, hi) if paired else None


def engine_host_ns(spans: List[list]) -> List[float]:
    """Per ``engine.step``: its duration less the ``engine.fetch`` spans
    inside it."""
    steps = [e for e in spans if e[0] == STEP]
    fetches = sorted((s, d) for n, s, d, *_ in spans if n == FETCH)
    out = []
    for _, s, d, *_ in steps:
        inside = sum(fd for fs, fd in fetches if s <= fs and fs + fd <= s + d)
        out.append(d - inside)
    return out


def reduce_spans(events: dict, window_s: float,
                 names_: Optional[dict] = None) -> dict:
    """Idle attribution and engine host time of a traced stretch
    ``window_s`` seconds long (all durations in seconds)."""
    nm = names_ or trace_lib.names()
    ops = trace_lib._device_lines(events, nm["op_lines"])
    spans = events.get("spans", [])
    n_steps = sum(1 for e in spans if e[0] == STEP)
    if not ops or not n_steps:
        return {"steps": 0, "window_s": window_s}
    segs = timeline(spans)
    mods = trace_lib._device_lines(events, nm["module_lines"])
    window = window_s * 1e9
    acc: Dict[str, float] = defaultdict(float)
    by_span: Dict[str, float] = defaultdict(float)
    offsets = []
    for plane, evs in ops.items():
        bounds = clock_offset(mods.get(plane, []), spans,
                              nm["programs"]["decode"])
        shift = sum(bounds) / 2 if bounds else 0.0
        offsets.append((bounds, shift))
        iv = trace_lib._union([(s + shift, s + d + shift)
                               for _, s, d in evs])
        gaps = [(b0, a1) for (_, b0), (a1, _) in zip(iv, iv[1:])]
        gap = sum(b - a for a, b in gaps)
        in_step = 0.0
        for open_, t in overlap(gaps, segs).items():
            by_span[open_[-1]] += t
            if STEP in open_:
                in_step += t
                acc["fetch" if FETCH in open_ else "engine"] += t
        acc["busy"] += sum(b - a for a, b in iv)
        acc["gap"] += gap
        # idle outside engine.step, and at the stretch's two edges
        acc["rest"] += gap - in_step + window - (iv[-1][1] - iv[0][0])
    n = len(ops)
    host = engine_host_ns(spans)
    return {
        "steps": n_steps,
        "window_s": window_s,
        "busy_s": acc["busy"] / n * 1e-9,
        "gap_idle_s": acc["gap"] / n * 1e-9,
        "idle_in_fetch_s": acc["fetch"] / n * 1e-9,
        "idle_in_engine_s": acc["engine"] / n * 1e-9,
        "idle_rest_s": acc["rest"] / n * 1e-9,
        "idle_by_span_s": {k: v / n * 1e-9 for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])},
        "engine_host_ms": sum(host) / len(host) * 1e-6,
        # per device plane: the causal range of the clock offset and the
        # shift applied, in ms
        "clock_ms": [[None if b is None else [b[0] * 1e-6, b[1] * 1e-6],
                      sh * 1e-6] for b, sh in offsets],
    }


def for_run(run) -> Optional[dict]:
    """The reduction of a traced run, made once and kept in ``run.trace``;
    None for an untraced run, a trace without device operations, or a
    program without spans."""
    t = run.trace
    if not t or not t.get("busy_s") or run.tracer is None:
        return None
    if "spans" not in t:
        ev = load_spans(trace_lib.find_xplane(run.tracer.dir))
        t["spans"] = reduce_spans(ev, t["window_s"])
    return t["spans"] if t["spans"]["steps"] else None
