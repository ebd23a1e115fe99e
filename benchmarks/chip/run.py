"""Run one cell of the chip benchmark once, and print its result line.

  python3 benchmarks/chip/run.py --workload <name> --seed <n> \
      --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` at the checkout's root. Set-up
(weights made on the chip from the seed, the program's engine, warm-up of
the cell's shapes, the traffic) runs from process start to the window,
and is reported as ``setup_s``. The window then drives the program for
``--seconds``. Afterwards the program's state is freed and a sample of its
answers is compared with the configuration's plain reference; each number
compared is printed beside its limit, as the last lines on standard error
and under ``checks``, last in the result.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the profiler records the window's last seconds and the
result carries its per-layer metrics, the device's busy and traced
seconds, and a breakdown of the trace. The last line of standard output is
that JSON object. Off a TPU, or with fewer chips than the cell asks for,
the command exits with status 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from harness import device, spec  # noqa: E402
from harness.clock import CompileCounter, use_checkout_cache  # noqa: E402
from harness.record import p95  # noqa: E402

NO_CHIP_STATUS = 3


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def info(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             chip_peaks: dict, t_start: float) -> dict:
    """Set up, measure, free and check one run of ``cell``; the result as
    the command prints it, ``checks`` last."""
    driver = cell.driver()
    if trace:
        driver.annotate()
    counter = CompileCounter()
    sess = driver.Session(cell, seed)
    c0 = counter.programs
    win = sess.measure(seconds, trace)
    win.compiles = counter.programs - c0
    win.setup_s = win.t0 - t_start
    win.peaks = chip_peaks
    peak = device.memory_peak_bytes(devices)
    sess.close()
    del sess
    gc.collect()
    late = p95([r.sent - r.due for r in win.requests])
    info(f"setup_s {win.setup_s!r} window_s {win.seconds!r} "
         f"requests {len(win.requests)} window_compiles {win.compiles} "
         f"send_lateness_p95_s {late!r}")
    t_check = time.perf_counter()
    checks = driver.check(cell, seed, win)
    info(f"check_s {time.perf_counter() - t_check!r}")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.reader.read(win)
        if value is None and not m.per_layer:
            raise RuntimeError(f"end-to-end metric {m.name} read nothing")
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}
    failed = sum(1 for r in win.requests
                 if r.error is not None or r.done is None)
    dev = device.describe(devices, peak)
    result = {"correct": all(c.ok for c in checks),
              "attempted": len(win.requests), "failed": failed,
              "metrics": metrics, "device": dev}
    if trace:
        if win.trace is None:
            raise RuntimeError("the traced span holds no device operation")
        dev["busy_s"] = win.trace.busy_s
        dev["window_s"] = win.trace.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in win.trace.device_ops],
            "idle_gaps": [list(x) for x in win.trace.idle_gaps]}
    for c in checks:
        info(f"check {c.name} {c.value!r} limit {c.limit!r} "
             f"{'ok' if c.ok else 'FAILED'}")
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = spec.resolve(args.workload)
        devices = device.require_chips(cell.chips)
    except (spec.SpecError, device.NoChip) as e:
        info(f"run.py: {e}")
        return NO_CHIP_STATUS
    kind = devices[0].device_kind
    info(f"device {kind} x{len(devices)} compile_cache "
         f"{use_checkout_cache(ROOT)}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, device.peaks(kind), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
