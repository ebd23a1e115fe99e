"""Find an open-loop cell's knee: the highest offered rate at which the
program keeps up, with no backlog growing through the window.

  python3 benchmarks/chip/tools/sweep.py --workload <name> --seed 7 \
      --seconds 10 --rates 5,10,15,20

One set-up, then one window per rate, in this one process. For each rate
it prints one JSON line: requests offered and answered, the cell's
end-to-end metrics at that rate, the p50 and p95 of due-to-done latency,
and how long after the window's close the last answer came (a backlog
shows as that drain time and the tail growing with the window).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import numpy as np  # noqa: E402

from harness import device, spec  # noqa: E402
from harness.clock import use_checkout_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    devices = device.require_chips(cell.chips)
    use_checkout_cache(ROOT)
    peaks = device.peaks(devices[0].device_kind)
    sess = cell.driver().Session(cell, args.seed)
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            win = sess.measure(args.seconds, rate=rate)
            win.peaks = peaks
            fin = win.finished()
            lat = [r.done - r.due for r in fin]
            out = {"rate_per_s": rate, "offered": len(win.requests),
                   "answered": len(fin),
                   "answered_in_window": sum(r.done <= win.t1 for r in fin),
                   "latency_p50_ms": 1e3 * float(np.percentile(lat, 50)),
                   "latency_p95_ms": 1e3 * float(np.percentile(lat, 95)),
                   "drain_after_close_s": max(r.done for r in fin) - win.t1,
                   "send_lateness_p95_ms": 1e3 * float(np.percentile(
                       [r.sent - r.due for r in win.requests], 95))}
            for m in cell.end_to_end:
                if m.name != "setup_s":
                    out[m.name] = m.reader.read(win)
            print(json.dumps(out), flush=True)
    finally:
        sess.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
