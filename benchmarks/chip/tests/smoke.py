"""Cells of BENCHMARK.json cut to the program's smoke widths, so that the
harness can be driven end to end on the CPU in a test. Only the sizes
change: the files, drivers, readers and references are the benchmark's."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec  # noqa: E402

SMOKE_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads",
              "intermediate_size": "d_ff", "vocab_size": "vocab_size"}

# CPU stand-in for the peaks table: what the CPU tests read is never a
# device number.
FAKE_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

# Smaller traffic, so that a CPU run answers within its window.
SMOKE_TRAFFIC = {
    "burst512": {"burst": 16},
    "stream": {"rate_per_s": 40},
    "chat": {"rate_per_s": 4, "engine": {"max_batch": 2,
                                          "max_new_tokens": 8},
             "output": {"min": 2, "median": 4, "max": 8},
             "prompt": {"min": 4, "median": 12, "max": 32},
             "check": {"requests": 8, "tokens": 64}},
    "rag": {"rate_per_s": 4, "engine": {"pad_buckets": [64], "max_batch": 2,
                                         "max_new_tokens": 4},
            "prompt": {"min": 16, "max": 64}, "output": {"min": 2, "max": 4},
            "check": {"requests": 3, "tokens": 8}},
}

# The widest logit gap at smoke widths, set between the program's readings
# there (0 to 0.016) and the float8 control's (0.13 to 0.25) on the CPU:
# the cell's own limit comes from chip readings at its size.
SMOKE_LIMITS = {"logit_gap": 0.06}


def smoke_cell(workload: str, mix: str = None):
    """The cell ``workload`` with the smoke widths of its program config
    and smaller traffic (``traffic/<mix>.json`` in place of its own, when
    given); returns (cell, program config)."""
    from repro.configs import get_config
    cell = spec.resolve(workload)
    if mix is not None:
        cell.traffic_name = mix
        cell.traffic = spec.load_json(BENCH / "traffic" / f"{mix}.json")
    conf = copy.deepcopy(cell.config)
    cfg = get_config(conf["registry"], smoke=True)
    for key, attr in SMOKE_KEYS.items():
        if key in conf:
            conf[key] = getattr(cfg, attr)
    if "max_position_embeddings" in conf:
        conf["max_position_embeddings"] = min(cfg.max_seq_len, 8192)
    traffic = copy.deepcopy(cell.traffic)
    for key, val in SMOKE_TRAFFIC.get(cell.traffic_name, {}).items():
        if isinstance(val, dict):
            traffic[key].update(val)
        else:
            traffic[key] = val
    if "corpus" in traffic:
        traffic["corpus"]["pool"] = 256
    conf["limits"] = {k: SMOKE_LIMITS.get(k, v)
                      for k, v in conf["limits"].items()}
    cell.config, cell.traffic = conf, traffic
    return cell, cfg


def run_smoke(workload: str, seed: int, seconds: float = 2.0,
              mix: str = None) -> dict:
    """One run of ``workload`` at smoke widths on whatever JAX has, past
    the harness's look for a chip; the result as the command prints it."""
    import time

    import jax
    import run
    cell, cfg = smoke_cell(workload, mix)
    driver = cell.driver()
    real = driver.program_config
    driver.program_config = lambda conf: cfg
    try:
        return run.run_cell(cell, seed, seconds, False, jax.devices(),
                            FAKE_PEAKS, time.perf_counter())
    finally:
        driver.program_config = real
