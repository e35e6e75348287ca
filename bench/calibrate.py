#!/usr/bin/env python3
"""Readings the benchmark's bounds and limits are set from; not part of
a benchmark run.  Several runs share one process, so set-up compiles once.

    python3 bench/calibrate.py control --workload <cell> --seconds <s> --seeds <n>...
    python3 bench/calibrate.py sweep --workload <cell> --seconds <s> --seeds <n>... --rates <ops/s>...

``control``: per seed, the numbers the check compares for the program and
for the control (the reference in the next lower precision, or with the
last acknowledged batch left out) over the same sampled replies.
``sweep``: the cell on each seed at each open-loop churn rate, with the
updater's lateness at the start and the end of the window.  Each run prints
one JSON line; like ``run.py``, it refuses to run without a TPU.
"""
import time

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("control", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    args = ap.parse_args(argv)

    from bench import graphs, harness, reference, workload

    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    bench = harness.load_benchmark()
    spec = harness.cell_spec(bench, args.workload)
    config = graphs.load_config(spec["config"])
    runs = ([(s, None) for s in args.seeds] if args.mode == "control"
            else [(s, r) for s in args.seeds for r in args.rates])
    for seed, rate in runs:
        traffic = workload.load_traffic(spec["traffic"])
        if rate is not None:
            traffic["churn"]["rate_ops_per_s"] = rate
        out = harness.run_cell(
            args.workload, seed, args.seconds, False,
            t_process=time.perf_counter(), config=config, traffic=traffic,
            bench=bench,
            control=reference.to_bfloat16 if args.mode == "control" else None)
        print(json.dumps({"calibrate": args.mode, "seed": seed, "rate": rate,
                          **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
