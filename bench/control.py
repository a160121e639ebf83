"""Readings behind a cell's correctness limit, taken on the chip.

    python3 bench/control.py --workload <name> --seconds 6 \
        --seeds 1,2,...,12 --control-seeds 101,102,103

For each of ``--seeds`` it runs the cell as configured (a short window at
the cell's own load and sizes) and prints the widest reference gap of a
served token; for each of ``--control-seeds`` it runs the same cell with
the program's int8 serving path switched on (``serve_quant``: int8 weights
and int8 KV blocks, the step below the configuration's bf16) and prints
the same reading. The limit in the configuration file lies between the
largest sound reading and the smallest control reading. One process, so
the programs compile once.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]

    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import harness

    if jax.devices()[0].platform != "tpu":
        sys.exit("bench/control.py: no TPU")
    c = harness.cell(args.workload, ROOT)
    plan = [(int(x), False) for x in args.seeds.split(",") if x] + \
           [(int(x), True) for x in args.control_seeds.split(",") if x]
    for seed, quant in plan:
        t0 = time.perf_counter()
        res = harness.run_cell(c, seed=seed, seconds=args.seconds,
                               trace=False, t_start=t0,
                               serve_quant=True if quant else None,
                               log=lambda s: None)
        ch = res["checks"]
        print(json.dumps({
            "seed": seed, "control": quant,
            "worst_gap": ch["worst_gap"]["value"],
            "wrong_lengths": ch["wrong_lengths"]["value"],
            "correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"],
            "seconds": time.perf_counter() - t0}), flush=True)
        del res
        gc.collect()


if __name__ == "__main__":
    main()
