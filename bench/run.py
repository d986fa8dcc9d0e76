#!/usr/bin/env python3
"""Run one cell of the benchmark on this machine's TPU and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (objects, weights, compiles, warm-up) runs first, then the window of
``--seconds``, then the check against the plain reference.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics untraced, its
per-layer metrics with ``--trace 1``), ``device`` and, last, ``checks``:
each number compared beside its limit, which also end standard error.
Without a TPU, or with fewer chips than the cell needs, it exits non-zero
and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    devices = harness.require_devices(cell.chips)
    run = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START, devices)
    print(harness.describe(run), file=sys.stderr)
    t_check = time.perf_counter()
    checked = harness.checks(run)
    print(f"bench: the check took {time.perf_counter() - t_check} s", file=sys.stderr)
    out = harness.result(run, bool(args.trace), checked)
    harness.print_checks(checked)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
