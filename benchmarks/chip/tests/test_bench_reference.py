"""The program's served answers held to the plain references at smoke
widths, through the entry points the window drives, and the control: the
reference computed in float8 in the program's place must fail the same
limits."""
import pytest

from smoke import run_smoke, smoke_cell

WORKLOADS = ["gector-base.burst512", "qwen2-0.5b.chat"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_served_answers_match_the_reference(workload):
    res = run_smoke(workload, seed=2 ** 33 + 7)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    names = set(res["metrics"])
    assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("workload,mix", [
    ("gector-base.burst512", "stream"), ("qwen2-0.5b.chat", "rag")])
def test_pending_mixes_drive_the_program(workload, mix):
    """The mixes defined for later cells (open-loop sentences, long
    prompts) run through the same drivers and checks."""
    res = run_smoke(workload, seed=99, mix=mix)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("workload", ["gector-base.burst512",
                                      "qwen2-0.5b.chat"])
def test_float8_control_is_not_correct(workload):
    cell, cfg = smoke_cell(workload)
    driver = cell.driver()
    real = driver.program_config
    driver.program_config = lambda conf: cfg
    try:
        sess = driver.Session(cell, 31)
        win = sess.measure(2.0)
        sess.close()
    finally:
        driver.program_config = real
    program = driver.check(cell, 31, win)
    control = driver.check(cell, 31, win, control="fp8")
    assert all(c.ok for c in program), program
    assert not all(c.ok for c in control), control
