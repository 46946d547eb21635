"""``chip_smoke.py`` off the chip: its serve and correctness phases at
``SMOKE`` size on the CPU, and its refusal to report success without a
TPU (the platform check lives in ``main``)."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import registry

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

SMOKE = registry.get_config(cs.ARCH, smoke=True)
SMALL = dict(max_len=64, prompt_lens=(8, 16, 24), new_tokens=(3, 6))


@pytest.fixture(scope="module")
def served():
    return cs.serve_phase(SMOKE, seed=cs.SEED, **SMALL)


def test_requests_are_seeded_and_staggered():
    a = cs.make_requests(SMOKE.vocab_size, seed=cs.SEED)
    b = cs.make_requests(SMOKE.vocab_size, seed=cs.SEED)
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in b]
    assert len(a) == cs.N_REQUESTS
    assert {len(r.prompt) for r in a} == set(cs.PROMPT_LENS)
    assert all(cs.NEW_TOKENS[0] <= r.max_new_tokens <= cs.NEW_TOKENS[1]
               for r in a)
    assert any(r.arrival_step > 0 for r in a)
    assert all(len(r.prompt) + r.max_new_tokens - 1 <= cs.MAX_LEN for r in a)


def test_serve_phase_answers_every_request(served):
    report, params = served
    assert report["requests"] == cs.N_REQUESTS
    assert report["prompt_lens"] == [8, 16, 24]
    assert 3 * cs.N_REQUESTS <= report["tokens"] <= 6 * cs.N_REQUESTS
    assert report["decode_batches"] > 0
    assert params is not None


def test_correctness_phase_matches_forward(served):
    _, params = served
    out = cs.correctness_phase(SMOKE, params, seed=cs.SEED, prompt_len=16,
                               max_len=SMALL["max_len"])
    assert out["positions"] == 16 + 4
    assert out["rel"] < 1e-5            # float32 SMOKE: far inside LOGIT_TOL


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_main_fails_without_a_tpu(where, tmp_path):
    """On the CPU, and in a directory holding only the script, it exits
    nonzero and prints no success line."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


_CACHE_PROBE = """
from repro.core.compile_cache import CompileCounter, use_persistent_cache
print(use_persistent_cache())
import jax, jax.numpy as jnp
with CompileCounter() as cc:
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(cc.compiled, cc.cache_hits)
"""


def _probe(env):
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    path, counts = out.stdout.strip().splitlines()[-2:]
    return path, tuple(int(x) for x in counts.split())


def test_compile_cache_honours_env_and_hits_on_second_run(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    path, (compiled, hits) = _probe(env)
    assert path == str(tmp_path / "cc") and compiled > 0 and hits == 0
    assert _probe(env) == (path, (0, compiled))


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout():
    from repro.core.compile_cache import CACHE_DIR
    assert CACHE_DIR == ROOT / ".jax_cache"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run(
        [sys.executable, "-c", "from repro.core.compile_cache import "
         "use_persistent_cache as u; import jax; p = u(); "
         "print(p == jax.config.jax_compilation_cache_dir, p)"],
        env=dict(env, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src")),
        capture_output=True, text=True, timeout=300)
    assert out.stdout.split() == ["True", str(ROOT / ".jax_cache")]
