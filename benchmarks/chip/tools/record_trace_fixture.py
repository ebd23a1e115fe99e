"""Record a small profiler trace on the chip, to hold the trace reduction's
assumptions (plane and line names, host and device on one clock) against a
real TPU trace, and to keep as a test fixture.

  python3 benchmarks/chip/tools/record_trace_fixture.py <out_dir>

Runs on the chip: a few jitted matrix products inside named host spans,
with one deliberate host sleep between them, traced with the Python tracer
off (as the benchmark traces). Copies the ``.xplane.pb`` to
``<out_dir>/small.xplane.pb`` and prints each plane's lines with their event
counts and first names, so the reduction can be written against what a TPU
trace really holds.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> int:
    dev = jax.devices()[0]
    print(f"platform {dev.platform} kind {dev.device_kind}")
    if dev.platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda a, b: jnp.tanh(a @ b) @ b)
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    b = jnp.ones((1024, 1024), jnp.bfloat16) * 0.001
    f(a, b).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        for i in range(3):
            with jax.profiler.TraceAnnotation(f"bench.step{i}"):
                f(a, b).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.step3"):
            f(a, b).block_until_ready()
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        os.makedirs(out_dir, exist_ok=True)
        shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(
        os.path.join(out_dir, "small.xplane.pb"))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r} events={len(evs)} first="
                  f"{[(e.name, e.start_ns, e.duration_ns) for e in evs[:4]]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
