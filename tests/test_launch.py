"""Entry-point plumbing: where the compile cache lands, and the server's
entry point running a config it is given."""

import os
import subprocess
import sys

import jax
import pytest

from repro.configs import get_smoke_config
from repro.launch import cache
from repro.launch.serve import run_serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("placed", ["/cache/from/env", None])
def test_compile_cache_follows_env_else_checkout(placed, monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = cache.configure_compile_cache()
        if placed:
            # JAX reads the variable itself: nothing is set in code
            assert got == placed
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == os.path.join(REPO, ".jax_cache") == cache.CHECKOUT_CACHE_DIR
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


_CACHE_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.launch.cache import configure_compile_cache
configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def test_compile_cache_lands_in_env_dir(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(REPO, "src"))
    subprocess.run([sys.executable, "-c", _CACHE_SCRIPT], check=True, env=env, timeout=300)
    assert os.listdir(tmp_path), "no compiled program was cached in JAX_COMPILATION_CACHE_DIR"


def test_run_serving_takes_a_config():
    cfg = get_smoke_config("llama3.2-1b").replace(n_layers=1, d_model=32, d_ff=64)
    engine = run_serving(n_requests=3, prompt_len=8, max_new=4, n_slots=2, cfg=cfg)
    assert engine.cfg is cfg
    assert [len(r.tokens) for r in engine.completed] == [4, 4, 4]
