#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses to run (exit 2, no result line) unless JAX's first device is a
TPU and JAX sees as many chips as the cell asks for.  ``--trace 0`` prints
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the window.  The last stdout line is one JSON object.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    spec = harness.cell_spec(harness.load_benchmark(), args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < spec["chips"]:
        print(f"bench: {args.workload} needs {spec['chips']} chips, JAX "
              f"sees {len(devices)}", file=sys.stderr)
        return 2
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_process=T_PROCESS)
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
