"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
(``bench/configs``) and a traffic mix (``bench/traffic``); per-layer
metrics are read by ``bench/metrics/<name>.py``. Weights and traffic come
from ``--seed``. Set-up builds the engine and warms every program shape
the cell's traffic reaches; then the open loop runs ``--seconds`` of
measured window (after the mix's warm-up stretch of arrivals) and drains.
With ``--trace 1`` the last seconds of the window are traced and the
per-layer metrics are printed instead of the end-to-end ones.

The last line of standard output is one JSON object; the numbers the
correctness check compared, each with its limit, are also the last lines
of standard error. Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.

``--rehearsal`` runs the same control flow on the CPU at tiny sizes with
every kernel in Pallas interpret mode; it prints no device metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / "bench_out"


def fail(msg: str, code: int = 1) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


def result_line(res: dict, c: dict, trace: bool) -> dict:
    wanted = c["per_layer"] if trace else c["end_to_end"]
    metrics = {m["name"]: {"value": res["metrics"][m["name"]],
                           "unit": m["unit"]}
               for m in wanted if res["metrics"].get(m["name"]) is not None}
    line = dict(correct=res["correct"], attempted=res["attempted"],
                failed=res["failed"], metrics=metrics, device=res["device"])
    t = res["run"].trace
    if trace and t and t.get("busy_s"):
        line["device"] = dict(line["device"], busy_s=t["busy_s"],
                              window_s=t["window_s"])
        line["breakdown"] = {"device_ops": t["device_ops"],
                             "idle_gaps": t["idle_gaps"]}
    line["checks"] = res["checks"]
    return line


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny sizes, kernels interpreted; control "
                         "flow only, no device metric")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]

    import jax

    # one fixed cache directory inside the checkout: the path is part of
    # the cache's key, and only the first run of a cell there compiles
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import harness

    try:
        c = harness.cell(args.workload, ROOT)
    except harness.BenchError as e:
        fail(str(e))
    devices = jax.devices()
    if args.rehearsal:
        import repro.kernels

        # every kernel op resolves its backend through this function:
        # the rehearsal takes the TPU's path through the Pallas interpreter
        repro.kernels.platform_backend = lambda: "interpret"
        c = harness.rehearsal_cell(c)
    else:
        if devices[0].platform != "tpu":
            fail(f"no TPU: JAX found platform {devices[0].platform!r}")
        if len(devices) < c["workload"]["chips"]:
            fail(f"the cell asks for {c['workload']['chips']} chips, JAX "
                 f"found {len(devices)}")
        from peaks import UnknownDevice, peaks_for
        try:
            peaks_for(devices[0].device_kind)
        except UnknownDevice as e:
            fail(str(e))
    trace_dir = OUT / "trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    res = harness.run_cell(c, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t_start=T_START,
                           trace_dir=trace_dir)
    for name, ch in res["checks"].items():
        print(f"check {name} {ch['value']} limit {ch['limit']}",
              file=sys.stderr)
    line = result_line(res, c, bool(args.trace))
    if args.rehearsal:
        print(json.dumps({"rehearsal": True, "correct": line["correct"],
                          "attempted": line["attempted"],
                          "failed": line["failed"],
                          "checks": line["checks"]}))
        return
    print(json.dumps(line))


if __name__ == "__main__":
    main()
