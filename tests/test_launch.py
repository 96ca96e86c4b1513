"""Entry-point plumbing: one process per chip, and the compile cache.

* With ``--rpc``/``--workers`` the launcher's own process never
  initialises JAX's backend (on a TPU host it would hold the chip its
  workers need); more workers than chips is a clear error.
* ``compile_cache.enable`` leaves a ``JAX_COMPILATION_CACHE_DIR`` from
  the environment alone, and otherwise uses one fixed directory in the
  checkout.
"""
import os
import subprocess
import sys
import textwrap
import types

import pytest

from repro.launch import compile_cache
from repro.serving import rpc


def _run(code: str, env_extra=None):
    env = {**os.environ, **(env_extra or {})}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r.stdout


def test_rpc_launcher_parent_never_initialises_backend(tmp_path):
    out = _run("""
        from jax._src import xla_bridge
        from repro.launch import serve
        done, m = serve.main(["--arch", "mamba2-1.3b", "--workers", "1",
                              "--requests", "2", "--max-new", "3",
                              "--slots", "2", "--max-len", "32"])
        assert len(done) == 2 and all(len(r.output) == 3 for r in done)
        print("BACKEND_INITIALISED", xla_bridge.backends_are_initialized())
    """, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert "BACKEND_INITIALISED False" in out, out[-2000:]


def test_worker_chips(monkeypatch):
    def fake_probe(stdout):
        return lambda *a, **k: types.SimpleNamespace(stdout=stdout)

    monkeypatch.setattr(rpc.subprocess, "run", fake_probe("cpu 1\n"))
    assert rpc.worker_chips(3) == [None, None, None]
    monkeypatch.setattr(rpc.subprocess, "run", fake_probe("tpu 4\n"))
    assert rpc.worker_chips(2) == [0, 1]
    monkeypatch.setattr(rpc.subprocess, "run", fake_probe("tpu 1\n"))
    with pytest.raises(ValueError, match="2 engine workers need one TPU "
                                         "chip each, but this host has 1"):
        rpc.worker_chips(2)


def test_chip_env_limits_worker_to_its_chip():
    env = rpc._chip_env(3)
    assert env["TPU_VISIBLE_CHIPS"] == "3"
    assert env["JAX_PLATFORMS"] == "tpu"          # no silent CPU fallback
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"


def test_compile_cache_from_environment(tmp_path):
    out = _run("""
        import os, jax, jax.numpy as jnp
        from repro.launch import compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        print("DIR", compile_cache.enable())
        jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
    """, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert f"DIR {tmp_path}" in out
    assert any(tmp_path.iterdir()), "nothing was cached in the given dir"


def test_compile_cache_default_is_fixed_in_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import os, jax
        from repro.launch import compile_cache
        d = compile_cache.enable()
        assert jax.config.jax_compilation_cache_dir == d
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == d
        print("DIR", d)
    """)], capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert f"DIR {os.path.join(root, '.jax_cache')}" in r.stdout
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
