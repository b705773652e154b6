"""chip_smoke.py: refuses to run without a TPU, keeps its compile cache
where it says, and its kernel and serve phases pass on the CPU at reduced
size (Pallas in interpret mode, the jnp oracle in the engine)."""
import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stdout
    assert '"ok"' not in proc.stdout


def test_compile_cache_dir(smoke, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert smoke.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert smoke.compile_cache_dir() == str(ROOT / ".jax_cache")


def test_kernel_phase_interpret(smoke):
    cfg = dataclasses.replace(get_config("qwen3-4b").reduced(),
                              num_heads=8, num_kv_heads=2, head_dim=128)
    smoke.kernel_check(cfg, seed=0)


def test_serve_phase_reduced(smoke):
    out = smoke.serve_phase(get_config("qwen3-4b").reduced(), seed=0)
    n_plan = len(smoke.ONLINE_PROMPTS) + smoke.N_QUESTIONS
    assert out["finished"] == n_plan and out["aborted"] == 1
    assert out["tokens_out"] >= (len(smoke.ONLINE_PROMPTS) * smoke.ONLINE_NEW
                                 + smoke.N_QUESTIONS * smoke.OFFLINE_NEW
                                 + smoke.ABORT_AFTER)
    assert out["prefix_hit_tokens"] > 0
    assert not any(out["leaks"].values())
    assert out["dropped_callbacks"] == 0
    assert not out["decode_kernel"]      # the CPU engine runs the oracle
