"""Readings that set a cell's correctness limits: the program's numbers and
the control's, on several seeds, at the cell's own size and load.

  python3 benchmarks/chip/tools/control.py --workload <name> \
      --seeds 11,12,13 --seconds 5 [--control fp8]

For each seed, in this one process: set up the cell, drive a short window
at the cell's own traffic, free the program, then compare its answers
with the reference (the program's readings) and, on the same sampled
answers, put the reference computed at ``--control`` precision in the
program's place (the control's readings). One JSON line per seed. The
lower reading of a limit is the largest the program gives over a dozen
seeds or more; the upper is the smallest the control gives.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from harness import device, spec  # noqa: E402
from harness.clock import use_checkout_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="fp8")
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    device.require_chips(cell.chips)
    use_checkout_cache(ROOT)
    driver = cell.driver()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        sess = driver.Session(cell, seed)
        win = sess.measure(args.seconds)
        sess.close()
        del sess
        gc.collect()
        prog = driver.check(cell, seed, win)
        ctrl = driver.check(cell, seed, win, control=args.control)
        out = {"workload": cell.name, "seed": seed,
               "requests": len(win.requests),
               "seconds": time.perf_counter() - t0,
               "program": {c.name: c.value for c in prog},
               "control": {c.name: c.value for c in ctrl}}
        if hasattr(driver, "look"):      # the numbers behind each reading
            out["look"] = {"program": driver.look(cell, seed, win)["heads"],
                           "control": driver.look(cell, seed, win,
                                                  args.control)["heads"]}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
