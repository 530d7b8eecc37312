"""The benchmark's float64 reference against the repository's own oracle,
and its state comparison on states whose answer is known."""
import pathlib

import numpy as np

from chipbench import gen, harness, reference

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_reference_matches_the_dict_oracle():
    from repro.core import oracle
    cfg = harness.load_json(DATA / "tiny_config.json")
    rel = gen.generate(cfg, 11)
    groups = reference.Groups(cfg, rel)
    buckets = reference.bucketize(rel, cfg["coarsening"])
    y = rel["dep_delay"].astype(np.float64)
    for t, cov in cfg["treatments"].items():
        dims = sorted(set(cov) | set(cfg["query_dims"]))
        _, kept = oracle.cem_oracle({d: buckets[d] for d in dims}, rel[t],
                                    np.ones(len(y), bool))
        sums = groups.sums(t, groups.weights(y, np.ones(len(y))))
        got = reference.estimate(groups, sums, t)
        assert got["n_groups"] == len(kept)
        assert np.isclose(got["ate"], oracle.ate_oracle(kept, rel[t], y),
                          rtol=1e-12, atol=1e-12)
        assert np.isclose(got["att"], oracle.att_oracle(kept, rel[t], y),
                          rtol=1e-12, atol=1e-12)


def _view(keys, sums, matched):
    want = reference.engine_state(np.zeros(len(keys), np.uint32),
                                  np.asarray(keys, np.uint32), sums,
                                  np.asarray(matched), ("a",))
    bound = np.where(np.abs(want[1]) >= 2.0 ** 24, 2.0 ** 26, 1.0)
    return want, bound


def test_state_error_exact_and_rounded_entries():
    sums = {"one": np.array([3.0, 1.0]), "y": np.array([5.0, 2.0]),
            "yy": np.array([2.0 ** 25, 4.0]), "t_a": np.array([1.0, 0.0]),
            "yt_a": np.array([2.0, 0.0]), "yyt_a": np.array([4.0, 0.0])}
    (keys, mat), bound = _view([7, 3], sums, [1, 0])
    assert list(keys) == [3, 7]             # sorted by key
    want = {"a": (keys, mat, bound)}
    assert reference.state_error({"a": (keys, mat.copy())}, want) == {
        "views_differing": 0, "exact_mismatch": 0, "large_rel_err": 0.0}
    rounded = mat.copy()
    rounded[rounded == 2.0 ** 25] += 4.0
    err = reference.state_error({"a": (keys, rounded)}, want)
    assert err["exact_mismatch"] == 0
    assert err["large_rel_err"] == 4.0 / 2.0 ** 25
    off = mat.copy()
    off[0, 0] += 1.0
    notes = []
    assert reference.state_error({"a": (keys, off)}, want, notes)[
        "exact_mismatch"] == 1
    assert len(notes) == 1
    assert reference.state_error({}, want)["views_differing"] == 1


def test_state_error_catches_groups_under_other_keys():
    """Two groups that swap keys keep the multiset of rows, but not the
    state group by group."""
    sums = {"one": np.array([3.0, 1.0]), "y": np.array([5.0, 2.0]),
            "yy": np.array([9.0, 4.0]), "t_a": np.array([1.0, 0.0]),
            "yt_a": np.array([2.0, 0.0]), "yyt_a": np.array([4.0, 0.0])}
    (keys, mat), bound = _view([3, 7], sums, [1, 0])
    want = {"a": (keys, mat, bound)}
    swapped = reference.engine_state(np.zeros(2, np.uint32),
                                     np.array([7, 3], np.uint32), sums,
                                     np.array([1, 0]), ("a",))
    assert reference.state_error({"a": swapped}, want)[
        "exact_mismatch"] > 0
    moved = (np.array([3, 8], np.uint64), mat)
    notes = []
    assert reference.state_error({"a": moved}, want, notes)[
        "views_differing"] == 1
    assert notes == ["a: 1 groups missing, 1 not in the reference"]


def test_keys_pack_as_the_engine_exports_them():
    """The reference's key layout against the program's own codec, on
    the relation's buckets."""
    import jax.numpy as jnp
    from repro.core.cem import make_codec
    from chipbench import system
    cfg = harness.load_json(DATA / "tiny_config.json")
    rel = gen.generate(cfg, 5)
    b = reference.bucketize(rel, cfg["coarsening"])
    specs = system.coarsen_specs(cfg)
    for view, dims in reference.dims_of(cfg).items():
        codec = make_codec({d: specs[d] for d in dims})
        hi, lo = codec.pack({d: jnp.asarray(b[d], jnp.int32) for d in dims},
                            jnp.ones(len(b[dims[0]]), bool))
        got = (np.asarray(hi, np.uint64) << np.uint64(32)) | np.asarray(
            lo, np.uint64)
        assert np.array_equal(got, reference.pack_keys(cfg, dims, b)), view
