"""Per-kernel interpret-mode validation: Pallas vs pure-jnp/numpy oracles,
swept over shapes, dtypes and block sizes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.coarsen import CoarsenSpec
from repro.core.keys import KeyCodec
from repro.core import cem, estimate_ate
from repro.core import oracle
from repro.kernels import (cem_keys_op, knn_topk_op,
                           logistic_newton_terms_op, segment_sums_op)
from repro.kernels import ref
from repro.kernels.ops import local_seg_ids


# ---------------------------------------------------------------- cem_keys
@pytest.mark.parametrize("n,d,block", [(512, 3, 128), (1000, 5, 512),
                                       (64, 1, 64), (4096, 8, 512)])
def test_cem_keys_matches_codec(n, d, block):
    rng = np.random.default_rng(n + d)
    X = rng.normal(0, 3, (n, d)).astype(np.float32)
    valid = rng.random(n) > 0.2
    specs = {}
    cutlists = []
    for j in range(d):
        k = int(rng.integers(1, 6))
        cuts = sorted(rng.normal(0, 2, k).tolist())
        specs[f"c{j}"] = CoarsenSpec.from_cutpoints(cuts)
        cutlists.append(cuts)
    # engine path: coarsen + codec pack (sorted field order = c0..c9 asc)
    codec = KeyCodec.from_cardinalities(
        {f"c{j}": specs[f"c{j}"].n_buckets for j in range(d)})
    from repro.core.coarsen import coarsen
    buckets = {f"c{j}": coarsen(jnp.asarray(X[:, j]), specs[f"c{j}"])
               for j in range(d)}
    want_hi, want_lo = codec.pack(buckets, jnp.asarray(valid))
    widths = [codec.widths[f"c{j}"] for j in range(d)]
    got_hi, got_lo = cem_keys_op(jnp.asarray(X), cutlists, widths,
                                 jnp.asarray(valid), block=block)
    np.testing.assert_array_equal(np.asarray(got_hi), np.asarray(want_hi))
    np.testing.assert_array_equal(np.asarray(got_lo), np.asarray(want_lo))
    # and against the standalone jnp ref
    cmax = max(len(c) for c in cutlists)
    cp = np.full((d, cmax), np.inf, np.float32)
    for j, c in enumerate(cutlists):
        cp[j, :len(c)] = c
    rh, rl = ref.cem_keys_ref(jnp.asarray(X), jnp.asarray(cp),
                              [len(c) for c in cutlists], widths,
                              jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(got_hi), np.asarray(rh))
    np.testing.assert_array_equal(np.asarray(got_lo), np.asarray(rl))


# ----------------------------------------------------------- segment_stats
@pytest.mark.parametrize("n,s,block", [(512, 4, 128), (2048, 7, 256),
                                       (300, 2, 128), (1024, 1, 512)])
def test_segment_sums_matches_segment_sum(n, s, block):
    rng = np.random.default_rng(n + s)
    # sorted segment ids with random run lengths
    n_segs = max(2, n // 7)
    seg = np.sort(rng.integers(0, n_segs, n)).astype(np.int32)
    vals = rng.normal(0, 1, (n, s)).astype(np.float32)
    got = segment_sums_op(jnp.asarray(vals), jnp.asarray(seg), n_segs,
                          block=block)
    want = jax.ops.segment_sum(jnp.asarray(vals), jnp.asarray(seg),
                               num_segments=n_segs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_segment_partials_ref_identity():
    """The partials oracle itself reduces to segment_sum after combine."""
    rng = np.random.default_rng(0)
    n, s, block = 512, 3, 128
    seg = np.sort(rng.integers(0, 40, n)).astype(np.int32)
    vals = rng.normal(0, 1, (n, s)).astype(np.float32)
    local = np.asarray(local_seg_ids(jnp.asarray(seg), block))
    assert local.min() >= 0 and local.max() < block
    partials = ref.segment_partials_ref(jnp.asarray(vals),
                                        jnp.asarray(local), block)
    from repro.kernels.segment_stats import combine_partials
    base = jnp.asarray(seg.reshape(-1, block)[:, 0])
    got = combine_partials(jnp.asarray(partials), base, 40)
    want = jax.ops.segment_sum(jnp.asarray(vals), jnp.asarray(seg),
                               num_segments=40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


# ---------------------------------------------------------------- knn_topk
@pytest.mark.parametrize("nq,nc,d,k,bq,bc", [
    (128, 256, 2, 1, 64, 128), (200, 333, 4, 3, 128, 128),
    (64, 64, 1, 5, 64, 64), (256, 1024, 8, 2, 128, 512)])
def test_knn_topk_matches_oracle(nq, nc, d, k, bq, bc):
    rng = np.random.default_rng(nq + nc + d + k)
    Q = rng.normal(0, 1, (nq, d)).astype(np.float32)
    C = rng.normal(0, 1, (nc, d)).astype(np.float32)
    cv = rng.random(nc) > 0.2
    dist, idx = knn_topk_op(jnp.asarray(Q), jnp.asarray(C), jnp.asarray(cv),
                            k, block_q=bq, block_c=bc)
    wd, wi = oracle.knn_oracle(Q, C, cv, k, caliper=np.inf)
    got = np.asarray(dist)
    ok = np.isfinite(wd)
    np.testing.assert_allclose(got[ok], wd[ok], rtol=1e-3, atol=3e-3)
    assert np.all(got[~ok] >= 1e30)
    # exact distance set agreement on clear-margin rows
    clear = ok & (np.abs(got - wd) < 1e-4)
    agree = np.asarray(idx)[clear] == wi[clear]
    assert agree.mean() > 0.98


def test_knn_topk_matches_jnp_ref():
    rng = np.random.default_rng(7)
    Q = rng.normal(0, 1, (128, 3)).astype(np.float32)
    C = rng.normal(0, 1, (256, 3)).astype(np.float32)
    cv = np.ones(256, bool)
    d2, idx = knn_topk_op(jnp.asarray(Q), jnp.asarray(C), jnp.asarray(cv),
                          k=4)
    rd, ri = ref.knn_topk_ref(jnp.asarray(Q), jnp.asarray(C),
                              jnp.asarray(cv), k=4)
    np.testing.assert_allclose(np.asarray(d2) ** 2, np.asarray(rd),
                               rtol=1e-3, atol=3e-3)


# ----------------------------------------------------------- logistic_grad
@pytest.mark.parametrize("n,d,block", [(1024, 4, 256), (3000, 9, 1024),
                                       (256, 2, 128)])
def test_logistic_newton_terms(n, d, block):
    rng = np.random.default_rng(n + d)
    X = rng.normal(0, 1, (n, d)).astype(np.float32)
    t = (rng.random(n) < 0.4).astype(np.float32)
    m = (rng.random(n) > 0.1).astype(np.float32)
    w = rng.normal(0, 0.5, d).astype(np.float32)
    g, H = logistic_newton_terms_op(jnp.asarray(X), jnp.asarray(t),
                                    jnp.asarray(m), jnp.asarray(w),
                                    block=block)
    rg, rH = ref.logistic_newton_terms_ref(jnp.asarray(X), jnp.asarray(t),
                                           jnp.asarray(m), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(g), np.asarray(rg), rtol=2e-4,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(H), np.asarray(rH), rtol=2e-4,
                               atol=2e-3)


# --------------------------------------------- kernels wired into the engine
def test_kernel_backed_cem_equals_engine():
    """End-to-end: CEM computed with kernel front-end (cem_keys_op +
    segment_sums_op) gives the same matched set as the jnp engine."""
    rng = np.random.default_rng(42)
    n = 2000
    x0 = rng.normal(0, 2, n).astype(np.float32)
    x1 = rng.normal(0, 2, n).astype(np.float32)
    t = (rng.random(n) < 0.3).astype(np.int32)
    y = rng.normal(0, 1, n).astype(np.float32)
    valid = rng.random(n) > 0.1
    from repro.data.columnar import Table
    table = Table.from_numpy(dict(x0=x0, x1=x1, t=t, y=y), valid)
    cuts = [[-2.0, 0.0, 2.0], [-1.0, 1.0]]
    specs = {"x0": CoarsenSpec.from_cutpoints(cuts[0]),
             "x1": CoarsenSpec.from_cutpoints(cuts[1])}
    engine = cem(table, "t", "y", specs)

    codec = KeyCodec.from_cardinalities(
        {k: s.n_buckets for k, s in specs.items()})
    X = np.stack([x0, x1], axis=1)
    widths = [codec.widths["x0"], codec.widths["x1"]]
    hi, lo = cem_keys_op(jnp.asarray(X), cuts, widths, jnp.asarray(valid))
    from repro.core.cem import cem_from_keys
    matched, _, groups = cem_from_keys(hi, lo, table["t"], table["y"],
                                       table.valid)
    np.testing.assert_array_equal(np.asarray(matched),
                                  np.asarray(engine.table.valid))
    a = estimate_ate(groups)
    b = estimate_ate(engine.groups)
    np.testing.assert_allclose(float(a.ate), float(b.ate), rtol=1e-5)

