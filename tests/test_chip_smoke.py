"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size.

The one-chip phase runs in this process; the four-chip comparison runs in
a subprocess with 4 forced host devices (the main pytest process keeps
exactly 1 device, as in tests/test_online_partitioned.py). The entry
point itself must refuse a CPU backend, and the vectorized float64
reference the smoke checks against must equal the dict oracle.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import oracle
from repro.launch import smoke

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run(code: str, env_extra=None, timeout=900):
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src"),
           "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=str(ROOT))


def test_single_chip_phase_tiny(tmp_path):
    lines = []
    out = smoke.run_single(smoke.TINY, seed=0, workdir=str(tmp_path),
                           log=lines.append)
    text = "\n".join(lines)
    assert "16,384 rows" in text and "bitwise equal" in text, text
    assert all(d == 1 for d in out["dispatches"])
    assert len(out["dispatches"]) >= smoke.TINY.n_batches // 2
    assert out["worst"] <= 1.0
    assert set(out["live_groups"]) == set(smoke.COVARIATES)


def test_mesh_phase_tiny_on_four_host_devices():
    proc = _run("""
        import jax
        assert jax.device_count() == 4, jax.devices()
        from repro.launch import smoke
        out = smoke.run_mesh(smoke.TINY, seed=1, n_devices=4)
        assert len(out["per_device"]) == 4, out
        print("MESH_SMOKE_OK")
        """, {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "MESH_SMOKE_OK" in proc.stdout
    assert "identical across partitioned, row-sharded and one-device" \
        in proc.stdout


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_chip_smoke_refuses_cpu_backend(args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"),
                           *args], capture_output=True, text=True,
                          timeout=300, env=env, cwd=str(ROOT))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((ROOT / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, str(lone)], capture_output=True,
                          text=True, timeout=300, env=env,
                          cwd=str(tmp_path))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_group_stats_oracle_equals_dict_oracle():
    rng = np.random.default_rng(5)
    n = 3000
    buckets = {"a": rng.integers(0, 6, n), "b": rng.integers(0, 4, n),
               "c": rng.integers(0, 3, n)}
    t = (rng.random(n) < 0.3 + 0.08 * buckets["a"]).astype(np.int32)
    y = np.round(rng.normal(10, 5, n) + 3 * t)
    valid = rng.random(n) > 0.1
    groups = oracle.cem_group_stats_oracle(buckets, t, y, valid)
    _, kept = oracle.cem_oracle(buckets, t, valid)
    assert groups["n_groups_matched"] == len(kept)
    for sub in (None, {"a": [0, 2, 5]}, {"a": [1], "c": [0, 2]}):
        got = oracle.ate_att_oracle(groups, sub)
        names = sorted(buckets)
        want_groups = {k: rows for k, rows in kept.items()
                       if all(k[names.index(d)] in set(v)
                              for d, v in (sub or {}).items())}
        assert got["n_groups"] == len(want_groups)
        np.testing.assert_allclose(
            got["ate"], oracle.ate_oracle(want_groups, t, y), rtol=1e-12)
        np.testing.assert_allclose(
            got["att"], oracle.att_oracle(want_groups, t, y), rtol=1e-12)


def test_compile_cache_follows_env_var(tmp_path):
    code = """
        import json, os
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
        path = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
        print(json.dumps(dict(path=path, default=str(DEFAULT_DIR),
                              config=jax.config.jax_compilation_cache_dir)))
        """
    cache = tmp_path / "cc"
    proc = _run(code, {"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert proc.returncode == 0, proc.stderr
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["path"] == got["config"] == str(cache)
    assert any(cache.iterdir())
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], capture_output=True,
        text=True, timeout=300, cwd=str(tmp_path),
        env={**env, "PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["path"] == got["config"] == got["default"] == str(
        ROOT / ".jax_cache")
