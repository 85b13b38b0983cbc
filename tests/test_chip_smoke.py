"""chip_smoke.py on the CPU: its phases at tiny sizes, its refusal to run
without a TPU, and where the entry points put the compile cache."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(ROOT, "src")


def _run(args, env_extra=None, cwd=ROOT):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=SRC, **(env_extra or {}))
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=300)


def test_smoke_exits_nonzero_without_tpu():
    r = _run([os.path.join(ROOT, "chip_smoke.py")])
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_smoke_solve_phase_on_cpu():
    """Both backends (host; scanned through the interpreted kernels) reach
    the Dinic cut on a tiny road instance with several dense blocks."""
    import chip_smoke
    out = chip_smoke.solve_phase(24, 0, check_kernels=False, max_rows=64)
    assert out["n"] == 24 * 24 and out["p"] > 1 and out["bs"] <= 64
    for backend in ("host", "scanned"):
        assert out[backend]["rel_gap"] <= chip_smoke.REL_TOL, backend


def test_smoke_serve_phase_on_cpu():
    import chip_smoke
    out = chip_smoke.serve_phase(12, 0)
    assert out["completed"] == chip_smoke.SERVE_REQUESTS
    assert out["parity"] <= chip_smoke.SERVE_PARITY


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and receives the entries; without it
    the cache is the fixed ``<repo>/.jax_cache``."""
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.launch import compile_cache
        path = compile_cache.enable()
        if RUN:
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.ones(8)).block_until_ready()
        print(json.dumps([path, jax.config.jax_compilation_cache_dir]))
    """).replace("RUN", str(from_env))
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if from_env else None
    r = _run(["-c", code], env)
    assert r.returncode == 0, r.stderr[-2000:]
    path, configured = json.loads(r.stdout.strip().splitlines()[-1])
    want = str(tmp_path) if from_env else os.path.join(ROOT, ".jax_cache")
    assert path == configured == want
    if from_env:
        assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())
