"""Multi-device tests for the distributed causal engine.

These run in a SUBPROCESS with --xla_force_host_platform_device_count=8 so
the main pytest process keeps seeing exactly 1 device (per the dry-run
isolation rule). Each scenario compares the distributed result against the
single-device engine.
"""
import subprocess
import sys
import textwrap

import pytest

SCRIPT_HEADER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
assert jax.device_count() == 8, jax.devices()
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
"""


def _run(body: str):
    code = SCRIPT_HEADER + textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600,
                          env={**__import__("os").environ,
                               "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


def test_distributed_cem_matches_single_device():
    # equivalence with the single-device engine — the same outputs the
    # pre-unification (private n_t/n_c layout) path was checked against —
    # plus the Neyman variance the unified cuboid stat schema adds
    out = _run("""
    from repro.core import CoarsenSpec, cem, estimate_ate
    from repro.core.cem import pack_keys
    from repro.core.distributed import make_distributed_cem
    from repro.data.columnar import Table

    rng = np.random.default_rng(0)
    n = 4096
    x0 = rng.integers(0, 6, n).astype(np.int32)
    x1 = rng.integers(0, 5, n).astype(np.int32)
    t = (rng.random(n) < 0.25 + 0.1 * x0 / 5).astype(np.int32)
    y = (2.0 * t + x0 + rng.normal(0, .3, n)).astype(np.float32)
    valid = rng.random(n) > 0.1
    table = Table.from_numpy(dict(x0=x0, x1=x1, t=t, y=y), valid)
    specs = {"x0": CoarsenSpec.categorical(6), "x1": CoarsenSpec.categorical(5)}

    # single-device reference (row-level variance via estimate_ate)
    res = cem(table, "t", "y", specs)
    want = estimate_ate(res.groups, table["y"], table["t"],
                        res.table.valid)

    # distributed
    codec, hi, lo = pack_keys(table, specs)
    f = make_distributed_cem(mesh, capacity=256)
    ate, att, var, ng, nt, nc, matched, overflow = f(
        hi, lo, table["t"], table["y"], table.valid)
    assert not bool(overflow)
    np.testing.assert_allclose(float(ate), float(want.ate), rtol=1e-4)
    np.testing.assert_allclose(float(att), float(want.att), rtol=1e-4)
    assert float(want.variance) > 0.0
    np.testing.assert_allclose(float(var), float(want.variance), rtol=1e-3)
    assert int(ng) == int(want.n_groups)
    np.testing.assert_allclose(float(nt), float(want.n_matched_treated))
    np.testing.assert_allclose(float(nc), float(want.n_matched_control))
    np.testing.assert_array_equal(np.asarray(matched),
                                  np.asarray(res.table.valid))
    print("DIST_CEM_OK")
    """)
    assert "DIST_CEM_OK" in out


def test_distributed_cem_overflow_flag():
    out = _run("""
    from repro.core import CoarsenSpec
    from repro.core.cem import pack_keys
    from repro.core.distributed import make_distributed_cem
    from repro.data.columnar import Table

    rng = np.random.default_rng(1)
    n = 4096
    x0 = rng.integers(0, 4096, n).astype(np.int32)  # ~unique keys
    t = (rng.random(n) < 0.5).astype(np.int32)
    y = rng.normal(0, 1, n).astype(np.float32)
    table = Table.from_numpy(dict(x0=x0, t=t, y=y))
    codec, hi, lo = pack_keys(table, {"x0": CoarsenSpec.categorical(4096)})
    f = make_distributed_cem(mesh, capacity=64)  # deliberately too small
    *_, overflow = f(hi, lo, table["t"], table["y"], table.valid)
    assert bool(overflow)
    print("OVERFLOW_OK")
    """)
    assert "OVERFLOW_OK" in out


def test_ring_knn_matches_quadratic():
    out = _run("""
    from repro.core.distributed import make_ring_knn
    from repro.core.matching import knn_quadratic, BIG

    rng = np.random.default_rng(2)
    n, d, k = 1024, 3, 4
    U = rng.normal(0, 1, (n, d)).astype(np.float32)
    cv = rng.random(n) > 0.3
    f = make_ring_knn(mesh, k=k)
    dist, idx = f(jnp.asarray(U), jnp.asarray(U), jnp.asarray(cv))
    wd, wi = knn_quadratic(jnp.asarray(U), jnp.asarray(U), jnp.asarray(cv),
                           k, caliper=np.inf)
    got, want = np.asarray(dist), np.asarray(wd)
    ok = want < 1e30
    np.testing.assert_allclose(got[ok], want[ok], rtol=1e-3, atol=3e-3)
    assert np.all(got[~ok] > 1e30)
    print("RING_KNN_OK")
    """)
    assert "RING_KNN_OK" in out


def test_distributed_newton_matches_single():
    out = _run("""
    from repro.core.distributed import make_distributed_newton
    from repro.core.propensity import fit_logistic, predict_ps

    rng = np.random.default_rng(3)
    n, d = 4096, 4
    X = rng.normal(0, 1, (n, d)).astype(np.float32)
    logits = 1.2 * X[:, 0] - 0.5 * X[:, 2]
    t = (rng.random(n) < 1/(1+np.exp(-logits))).astype(np.float32)
    m = (rng.random(n) > 0.1).astype(np.float32)

    # single-device reference on standardized-with-bias features
    mu = (X * m[:, None]).sum(0) / m.sum()
    sd = np.sqrt((m[:, None] * (X - mu) ** 2).sum(0) / m.sum() + 1e-12)
    Xb = np.concatenate([(X - mu) / sd, np.ones((n, 1))], 1).astype(np.float32)
    f = make_distributed_newton(mesh)
    w = f(jnp.asarray(Xb), jnp.asarray(t), jnp.asarray(m))

    model = fit_logistic(jnp.asarray(X), jnp.asarray(t),
                         jnp.asarray(m > 0))
    np.testing.assert_allclose(np.asarray(w), np.asarray(model.w),
                               rtol=2e-3, atol=2e-3)
    print("NEWTON_OK")
    """)
    assert "NEWTON_OK" in out


def test_compressed_psum_close_to_exact():
    out = _run("""
    from jax.sharding import PartitionSpec as P
    from repro.optim.grad_compress import compressed_psum_mean

    rng = np.random.default_rng(4)
    g = rng.normal(0, 0.01, (8, 512)).astype(np.float32)

    def body(x):
        return compressed_psum_mean(x[0], "data")[None]

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data", None),),
                              out_specs=P(None), check_vma=False))
    got = np.asarray(f(jnp.asarray(g)))[0]
    want = g.mean(axis=0)
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 0.02, err
    print("COMPRESS_OK")
    """)
    assert "COMPRESS_OK" in out
