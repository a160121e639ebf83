"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so the second is tested on a small recorded trace
(``tests/bench/data``):

1. ``load_xplane``: the ``.xplane.pb`` the JAX profiler writes -> plain
   events ``[name, start_ns, duration_ns]`` by plane and line, keeping the
   device planes and the benchmark's own host spans (``bench.*``) with
   the host events on their thread.
2. ``reduce_events``: busy time (the union of the device's operation
   intervals), idle share, device time of each program and kernel named
   in ``programs.json``, the operations that took most time, and the
   longest idle gaps with what the host was doing in each.
"""

from __future__ import annotations

import json
import pathlib
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

NAMES = pathlib.Path(__file__).resolve().parent / "programs.json"


def names() -> dict:
    return json.loads(NAMES.read_text())


def find_xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: pathlib.Path) -> dict:
    """Plain events of the device planes and of the host thread that
    carries the benchmark's spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out: Dict[str, Dict[str, list]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            out[plane.name] = {
                line.name: [[e.name, e.start_ns, e.duration_ns]
                            for e in line.events]
                for line in plane.lines}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in line.events]
                if any(e[0].startswith("bench.") for e in evs):
                    out.setdefault("/host:CPU", {})[line.name] = evs
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _matches(name: str, patterns) -> bool:
    return any(re.search(p, name) for p in patterns)


def _device_lines(events: dict, line_names) -> Dict[str, list]:
    """The first of ``line_names`` present on each device plane that has
    events there."""
    out = {}
    for plane, lines in events.items():
        if not plane.startswith("/device:"):
            continue
        for ln in line_names:
            if lines.get(ln):
                out[plane] = lines[ln]
                break
    return out


_SUFFIX = re.compile(r"(\.(\d+|remat\w*|clone\w*))+$")
CONTAINERS = ("while", "conditional", "call")


def op_kind(name: str) -> str:
    """An HLO operation's kind and result type, without its instance
    number or layout: ``%copy.97.remat2 = bf16[81,32,128,96]{...} copy(..``
    -> ``copy bf16[81,32,128,96]``."""
    head, _, rest = name.partition(" = ")
    kind = _SUFFIX.sub("", head.strip().lstrip("%"))
    rtype = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0]) if rest else ""
    return f"{kind} {rtype}".strip()


def _host_label(host: List[list], t: float) -> str:
    """The innermost host event covering time ``t``."""
    best = None
    for name, s, d in host:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no host span"


def reduce_events(events: dict, window_s: float,
                  names_: Optional[dict] = None, top: int = 10) -> dict:
    """Numbers of a traced window ``window_s`` seconds long."""
    nm = names_ or names()
    ops = _device_lines(events, nm["op_lines"])
    mods = _device_lines(events, nm["module_lines"])
    if not ops:
        return {"busy_s": None, "window_s": window_s}
    busy, gaps_all = [], []
    by_op: Dict[str, float] = defaultdict(float)
    kernel_s: Dict[str, float] = defaultdict(float)
    kernel_n: Dict[str, int] = defaultdict(int)
    for plane, evs in ops.items():
        iv = _union([(s, s + d) for _, s, d in evs])
        busy.append(sum(b - a for a, b in iv) * 1e-9)
        gaps_all += [(b0, a1) for (_, b0), (a1, _) in zip(iv, iv[1:])]
        for name, _, d in evs:
            kind = op_kind(name)
            if kind.split(" ")[0] not in CONTAINERS:
                by_op[kind] += d * 1e-9
            for k, pats in nm["kernels"].items():
                if _matches(name, pats):
                    kernel_s[k] += d * 1e-9
                    kernel_n[k] += 1
    program_s: Dict[str, float] = defaultdict(float)
    program_n: Dict[str, int] = defaultdict(int)
    for evs in mods.values():
        for name, _, d in evs:
            for p, pats in nm["programs"].items():
                if _matches(name, pats):
                    program_s[p] += d * 1e-9
                    program_n[p] += 1
    host = [e for line in events.get("/host:CPU", {}).values() for e in line]
    gaps = sorted(gaps_all, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "program_s": dict(program_s), "program_n": dict(program_n),
        "kernel_s": dict(kernel_s), "kernel_n": dict(kernel_n),
        "device_ops": sorted(([k, v] for k, v in by_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_host_label(host, (a + b) / 2), (b - a) * 1e-9]
                      for a, b in gaps],
    }


def reduce_dir(trace_dir: pathlib.Path, run, span: tuple) -> dict:
    """Reduce the trace under ``trace_dir``; ``span`` is the traced
    stretch on the benchmark's clock. Adds what the kernel metric divides
    by, from the benchmark's own step records: the decode rows of every
    step of the stretch."""
    ev = load_xplane(find_xplane(trace_dir))
    out = reduce_events(ev, span[1] - span[0])
    steps = [s for s in run.steps if span[0] <= s.t0 and s.t1 <= span[1]]
    out["decode_lens"] = [s.lens for s in steps if s.decode_rows]
    return out
