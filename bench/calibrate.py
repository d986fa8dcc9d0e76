#!/usr/bin/env python3
"""Read the numbers the check compares, for the program, its control and its faults.

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 ... [--faults 3]

On the chip, at the cell's own size, in one process: for each seed the
program runs through the harness (set-up, warm-up, a short window) and is
compared with the float32 reference; the control, the reference computed
with float8 matmuls, is put in the program's place and compared the same
way.  With ``--faults n``, the first ``n`` seeds also run the program with
each fault of ``bench/faults.py`` planted.  Each reading is one JSON line
on standard output; the last line gives, for each number, the largest
reading of the sound program (the lower reading) and the smallest of the
control and of each fault (upper readings), from which ``limits`` in the
configuration file are set.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()

    from bench import faults, harness, reference

    cell = harness.load_cell(args.workload)
    devices = harness.require_devices(cell.chips)
    worst = {}

    def note(kind, seed, numbers, extra=None):
        print(json.dumps({"kind": kind, "seed": seed, **numbers, **(extra or {})}), flush=True)
        for name, value in numbers.items():
            key = (kind, name)
            best = max if kind == "program" else min
            worst[key] = value if key not in worst else best(worst[key], value)

    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        run = harness.run_cell(cell, seed, args.seconds, False, t0, devices)
        ref = harness.reference_readings(run, "fp32")
        checked = harness.checks(run, ref)
        note(
            "program",
            seed,
            {k: c["value"] for k, c in checked.items()},
            {"losses": run.program.losses, "ref_losses": ref.losses},
        )
        control = harness.reference_readings(run, "fp8")
        note("control", seed, reference.compare(control, ref), {"losses": control.losses})
        if i < args.faults:
            for name, plant in faults.FAULTS.items():
                if name == "half_batch" and cell.batch < 2:
                    continue  # a batch of one has no half to leave out
                with plant():
                    t1 = time.perf_counter()
                    bad = harness.run_cell(cell, seed, args.seconds, False, t1, devices)
                note(name, seed, {k: c["value"] for k, c in harness.checks(bad, ref).items()})
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s", file=sys.stderr, flush=True)
    print(json.dumps({f"{kind}.{name}": v for (kind, name), v in sorted(worst.items())}))


if __name__ == "__main__":
    main()
