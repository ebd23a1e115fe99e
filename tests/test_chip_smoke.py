"""chip_smoke.py's phases on the CPU at the smoke configs: the script's
serving logic and its failure checks, at no chip cost. The chip run itself
is ``python chip_smoke.py`` on a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config
from repro.serving import ServingEngine

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


QWEN = get_config("qwen2-0.5b", smoke=True)
SMALL_DECODER = dict(buckets=(16, 32), max_batch=2, max_new_tokens=8)


def test_decoder_phase_serves_and_checks(chip_smoke):
    facts = chip_smoke.decoder_phase(QWEN, **SMALL_DECODER)
    assert facts["requests"] == 9
    assert facts["window"]["jit_compiles"] == 0
    assert facts["first_token"] == facts["reference_argmax"]


def test_decoder_phase_fails_on_compiles_in_window(chip_smoke, monkeypatch):
    """Without warmup the served window compiles, and the phase says so."""
    monkeypatch.setattr(ServingEngine, "warmup", lambda self, **kw: None)
    with pytest.raises(chip_smoke.SmokeFailure, match="compiles inside"):
        chip_smoke.decoder_phase(QWEN, **SMALL_DECODER)


def test_encoder_phase_serves_and_checks(chip_smoke):
    facts = chip_smoke.encoder_phase(get_config("gector-base", smoke=True))
    assert facts["sentences"] == 8
    assert facts["tag_logits_vs_forward"] <= chip_smoke.REL_TOL


def test_kernel_phase_interpreted(chip_smoke):
    errs = chip_smoke.kernel_phase(QWEN, interpret=True, batch=1,
                                   buckets=(128,))
    assert set(errs) >= {"int8_matmul_up_vs_ref", "decode_attention_vs_ref",
                         "flash_attention_128_vs_ref",
                         "flash_attention_128_vs_model"}


def test_kernel_phase_fails_on_wrong_kernel(chip_smoke, monkeypatch):
    real = chip_smoke.int8_matmul
    monkeypatch.setattr(chip_smoke, "int8_matmul",
                        lambda *a, **kw: real(*a, **kw) * 1.1)
    with pytest.raises(chip_smoke.SmokeFailure, match="int8_matmul_up"):
        chip_smoke.kernel_phase(QWEN, interpret=True, batch=1,
                                buckets=(128,))


def _run_script(path: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(path)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_cpu_backend():
    res = _run_script(ROOT / "chip_smoke.py", ROOT)
    assert res.returncode != 0
    assert "platform cpu" in res.stdout
    assert '"ok"' not in res.stdout


def test_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    res = _run_script(tmp_path / "chip_smoke.py", tmp_path)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir_rule(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins; otherwise one fixed, git-ignored
    directory at the checkout root."""
    import jax
    from repro.compile_cache import enable_compile_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(ROOT / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
