"""Read, on the chip at a cell's own size, what the limits of `correct` are
set from: the numbers compared, for sound runs of the program over many seeds
and for the control (the reference with fp8 matmuls, put in the program's
place) over a few.  One process, one compile; a training cell's
readings need no window.

    python3 benchmarks/tools/calibrate.py <cell> --seeds 1,2,3 --control 3

Prints one JSON line per seed: {"seed", "sound": {...}, "fp8": {...}?,
"fp8_correct": false?}; `--leaves` adds the first gradient's readings by leaf.
`--fault N` reads, on the first N seeds, the reference with half of the batch
left out (a driver that takes the mode "half"), put in the program's place.
"""

import argparse
import gc
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0,
                    help="run the control on the first N seeds")
    ap.add_argument("--fault", type=int, default=0,
                    help="run the half-batch fault on the first N seeds")
    ap.add_argument("--leaves", action="store_true",
                    help="also print the first gradient's readings by leaf")
    args = ap.parse_args()
    from benchmarks import check, harness

    wl, cfg, traffic = harness.load_cell(args.cell)
    if harness.device_gate(int(wl["chips"])) is None:
        return 3
    harness.enable_cache()
    make = importlib.import_module("benchmarks.drivers." + cfg["driver"]).Driver
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        drv = make(cfg["fields"], traffic, seed, int(wl["chips"]))
        drv.warm_up()
        drv.free()
        prog = drv.program_side()
        ref = drv.reference_side(
            None, prog["priority_after"] != drv.priority0())
        row = {"seed": seed, "steps": drv.first_learning["steps"],
               "sound": check.compare(prog, ref, drv.params0)}
        if args.leaves and prog["grad1"] is not None:
            row["sound_leaves"] = check.first_gradient_by_leaf(
                prog["grad1"], ref["grad1"])
        for mode in ["fp8"] * (i < args.control) + ["half"] * (i < args.fault):
            ctrl = drv.reference_side(mode, None)
            row[mode] = check.compare(ctrl, ref, drv.params0)
            row[mode + "_correct"] = check.verdict(
                {**row[mode], "first_steps_missing": 0.0,
                 "window_steps_missing": 0.0}, wl["limits"],
                wl.get("read_not_compared", ()))[0]
            if args.leaves:
                row[mode + "_leaves"] = check.first_gradient_by_leaf(
                    ctrl["grad1"], ref["grad1"])
        print(json.dumps(row), flush=True)
        del drv, prog, ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
