"""The harness is driven by data: BENCHMARK.json resolves to files, a new
cell is found by its files alone, the traffic is a function of the seed,
and the command refuses to measure anything but a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from smoke import BENCH, ROOT  # noqa: F401  (puts the harness on sys.path)

from harness import spec, traffic  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in BENCHMARK["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_to_its_files(workload):
    cell = spec.resolve(workload)
    assert cell.driver().Session and cell.driver().check
    ref = cell.reference()
    assert ref.make_weights
    names = {m.name for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        entry = next(e for e in BENCHMARK["per_layer"] if e["name"] == m.name)
        assert entry["moves"] in names
    for m in cell.end_to_end + cell.per_layer:
        assert callable(m.reader.read)


def test_benchmark_file_keeps_the_contract_shapes():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks/chip"]
    assert 1 <= b["run_seconds"] <= 51
    metrics = b["end_to_end"] + b["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("benchmarks/chip/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    layers = {}
    for m in b["per_layer"]:
        layers.setdefault(m["layer"], set()).add(m["name"])
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] == 1
        assert len(w["why"]) <= 200


def test_added_workload_is_found_by_its_files(tmp_path):
    """A later cell, configuration, traffic mix and metric arrive as new
    files and entries; the harness finds them without an edit."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    b = json.loads(json.dumps(BENCHMARK))
    conf = json.loads((BENCH / "configs" / "qwen2_0_5b.json").read_text())
    conf["name"] = "qwen2-0.5b-copy"
    (bench_dir / "configs" / "qwen2_copy.json").write_text(json.dumps(conf))
    (bench_dir / "traffic" / "tiny.json").write_text(json.dumps(
        dict(json.loads((BENCH / "traffic" / "chat.json").read_text()),
             rate_per_s=1)))
    (bench_dir / "metrics" / "tokens_out.py").write_text(
        "def read(win):\n    return sum(r.n_out for r in win.finished())\n")
    b["configs"].append({"name": "qwen2-0.5b-copy", "source": "x",
                         "file": "benchmarks/chip/configs/qwen2_copy.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "qwen2-0.5b-copy.tiny",
                           "config": "qwen2-0.5b-copy", "traffic": "tiny",
                           "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "tokens_out", "unit": "tokens",
                           "better": "higher", "source": "program_counter",
                           "layer": "continuous scheduler",
                           "moves": "ttft_p95_ms",
                           "workloads": ["qwen2-0.5b-copy.tiny"]})
    for m in b["end_to_end"]:
        if "workloads" in m and "qwen2-0.5b.chat" in m["workloads"]:
            m["workloads"].append("qwen2-0.5b-copy.tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.resolve("qwen2-0.5b-copy.tiny", root=tmp_path,
                        bench_dir=bench_dir)
    assert cell.traffic["rate_per_s"] == 1
    assert cell.config["name"] == "qwen2-0.5b-copy"
    assert [m.name for m in cell.per_layer] == ["tokens_out"]
    assert {m.name for m in cell.end_to_end} == {"ttft_p95_ms",
                                                 "tpot_p95_ms", "setup_s"}
    assert cell.driver().__file__.startswith(str(bench_dir))


def test_metric_without_workloads_follows_its_moves(tmp_path):
    """A metric entry with no ``workloads`` list is reported where its
    end-to-end metric is (per-layer) or in every cell (end-to-end)."""
    b = json.loads(json.dumps(BENCHMARK))
    b["per_layer"].append({"name": "dec_queue_p95_ms.any", "unit": "ms",
                           "better": "lower", "source": "program_span",
                           "layer": "continuous scheduler",
                           "moves": "ttft_p95_ms"})
    b["per_layer"] = [m for m in b["per_layer"]
                      if m["name"] == "dec_queue_p95_ms.any"]
    b["end_to_end"] = [m for m in b["end_to_end"]
                       if m["name"] in ("setup_s", "ttft_p95_ms")]
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    shutil.copy(bench_dir / "metrics" / "dec_queue_p95_ms.py",
                bench_dir / "metrics" / "dec_queue_p95_ms.any.py")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    for workload in WORKLOADS:
        cell = spec.resolve(workload, root=tmp_path, bench_dir=bench_dir)
        e2e = {m.name for m in cell.end_to_end}
        assert "setup_s" in e2e
        assert ([m.name for m in cell.per_layer]
                == (["dec_queue_p95_ms.any"] if "ttft_p95_ms" in e2e
                    else []))


def test_missing_file_is_refused(tmp_path):
    b = json.loads(json.dumps(BENCHMARK))
    b["workloads"][0]["traffic"] = "no_such_mix"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    shutil.copytree(BENCH / "configs", tmp_path / "benchmarks/chip/configs")
    with pytest.raises(spec.SpecError, match="no_such_mix"):
        spec.resolve(b["workloads"][0]["name"], root=tmp_path)


@pytest.mark.parametrize("mix", ["chat", "rag", "stream", "burst512"])
def test_traffic_is_a_function_of_the_seed(mix):
    t = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    if "corpus" in t:
        t["corpus"]["pool"] = 64

    def draw(seed):
        out = {}
        if t["kind"] == "open_poisson":
            out["due"] = traffic.arrivals(t, 3.0)
        if "prompt" in t:
            out["prompts"] = traffic.prompts(t, 20, 1000,
                                             traffic.rng_for(seed, "p"))
            out["budgets"] = traffic.output_budgets(t, 20)
        if "corpus" in t:
            out["pool"] = traffic.sentence_pool(t, 30522,
                                                traffic.rng_for(seed, "o"))
        return out

    big = 2 ** 33 + 12345
    a, again, b = draw(big), draw(big), draw(12345)
    for key in a:
        x, y, z = a[key], again[key], b[key]
        if isinstance(x, list):
            assert all(np.array_equal(p, q) for p, q in zip(x, y))
            assert not all(np.array_equal(p, q) for p, q in zip(x, z))
            if key == "prompts":   # the shared sizes, in the shared order
                assert list(map(len, x)) == list(map(len, z))
            else:                  # the pool, in an order of the seed's
                assert sorted(map(len, x)) == sorted(map(len, z))
        else:    # the schedule is every seed's
            assert np.array_equal(x, y) and np.array_equal(x, z)


def test_lengths_follow_the_mix():
    chat = json.loads((BENCH / "traffic" / "chat.json").read_text())
    p = traffic.lengths(chat["prompt"], 1000)
    o = traffic.lengths(chat["output"], 1000)
    assert p.min() >= 16 and p.max() <= 128 and 60 <= np.median(p) <= 68
    assert o.min() >= 32 and o.max() <= 256 and 90 <= np.median(o) <= 102
    due = traffic.arrivals(dict(chat, rate_per_s=10.0), 30.0)
    assert len(due) == 300 and 27 < due[-1] < 31


def _run_command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         WORKLOADS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    out = _run_command(ROOT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    """Without the program beside it the command fails and prints no
    result: the benchmark holds the yardstick, not the system."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_command(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_pick_rate_takes_four_fifths_of_the_knee(tmp_path):
    rows = [dict(rate_per_s=4, offered=48, answered=48, drain_after_close_s=1.0),
            dict(rate_per_s=8, offered=96, answered=96, drain_after_close_s=2.5),
            dict(rate_per_s=12, offered=144, answered=144,
                 drain_after_close_s=9.0),
            dict(rate_per_s=15, offered=180, answered=170,
                 drain_after_close_s=60.0)]
    sweep = tmp_path / "sweep.out"
    sweep.write_text("setup line\n" + "\n".join(json.dumps(r) for r in rows))
    mix = tmp_path / "mix.json"
    mix.write_text(json.dumps({"kind": "open_poisson", "rate_per_s": 1}))
    tool = BENCH / "tools" / "pick_rate.py"
    out = subprocess.run([sys.executable, str(tool), str(sweep), str(mix),
                          "4.0"], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert json.loads(mix.read_text())["rate_per_s"] == 6.4
    rows[0]["drain_after_close_s"] = rows[1]["drain_after_close_s"] = 30.0
    sweep.write_text("\n".join(json.dumps(r) for r in rows))
    out = subprocess.run([sys.executable, str(tool), str(sweep), str(mix),
                          "4.0"], capture_output=True, text=True)
    assert out.returncode == 1
