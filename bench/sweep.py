"""Find a cell's knee on the chip: the highest offered rate the engine
sustains without a growing backlog.

    python3 bench/sweep.py --workload <name> --rates 2,3,4,5 --seconds 20 --seed 1

One process builds and warms the engine once, then offers each rate in
turn (the cell's traffic mix with ``rate_rps`` replaced), draining and
emptying the engine between rates. For each rate it prints one JSON line:
offered and completed requests per second, tokens per second, the time-
to-first-token and gap tails, and the queue wait of the requests due in
each half of the window. A rate is sustained when the later half's 90th
percentile queue wait is no more than 1.5 times the earlier half's plus
250 ms and every request due in the window finished within the drain.
A cell below the knee offers 0.8 times the highest sustained rate.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def half_waits(run) -> tuple:
    from harness import percentile

    mid = (run.win[0] + run.win[1]) / 2
    halves = ([], [])
    for i in run.window:
        r = run.reqs[i]
        wait = ((r.admit or run.drain_deadline) - r.due) * 1e3
        halves[r.due >= mid].append(wait)
    return tuple(percentile(h, 90) for h in halves)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]

    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import harness

    if jax.devices()[0].platform != "tpu":
        sys.exit("bench/sweep.py: no TPU")
    c = harness.cell(args.workload, ROOT)
    s = harness.prepare(c, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        tr = dict(c["traffic"], rate_rps=rate)
        run = harness.open_loop(s, tr, args.seed, args.seconds)
        e2e = harness.end_to_end(run, 0.0)
        first, second = half_waits(run)
        failed = sum(1 for i in run.window if run.reqs[i].done is None)
        done = sum(1 for i in run.window
                   if run.reqs[i].done is not None
                   and run.reqs[i].done < run.win[1])
        sustained = (failed == 0 and first is not None and second is not None
                     and second <= 1.5 * first + 250.0)
        print(json.dumps({
            "rate_rps": rate, "window_requests": len(run.window),
            "completed_in_window_rps": done / run.seconds,
            "tokens_per_s": e2e["tokens_per_s"],
            "ttft_p50_ms": e2e["ttft_p50_ms"],
            "ttft_p90_ms": harness.percentile(run.ttft_ms(), 90),
            "itl_p99_ms": e2e["itl_p99_ms"],
            "queue_wait_p90_ms_halves": [first, second],
            "failed": failed, "sustained": sustained,
            "compiles_in_window": run.counters["compiles_in_window"]}),
            flush=True)


if __name__ == "__main__":
    main()
