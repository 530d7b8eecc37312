"""Contract checker: every rule fires on a seeded violation, stays quiet
on the clean idiom, and the machinery (suppressions, select/ignore,
baselines, CLI exit codes) behaves. The repo itself must scan clean.

Fixtures are tiny synthetic modules written under ``tmp_path``; each
declares ``__engine_owned__ = True`` so path-based scoping never matters
for the rule under test.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.analysis.lint import (Finding, load_baseline, run_lint,
                                 split_baselined, write_baseline)

REPO = Path(__file__).resolve().parent.parent

OWNED = "__engine_owned__ = True\n"
_D = textwrap.dedent


def _lint_snippet(tmp_path, source, name="mod.py", **kw):
    f = tmp_path / name
    f.write_text(textwrap.dedent(source))
    return run_lint([f], root=tmp_path, **kw)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------ ZQL001
def test_zql001_fires_on_raw_jit(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        import jax

        def build(fn):
            return jax.jit(fn)
        """))
    assert _rules(out) == ["ZQL001"]
    assert out[0].line == 5


def test_zql001_fires_on_pjit_and_aliases(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        from jax import jit as J
        from jax.experimental.pjit import pjit

        def build(fn):
            return J(fn), pjit(fn)
        """))
    assert [f.rule for f in out] == ["ZQL001", "ZQL001"]


def test_zql001_quiet_on_counted_jit_and_host_modules(tmp_path):
    assert _lint_snippet(tmp_path, OWNED + _D("""\
        from repro.launch.trace import counted_jit

        def build(fn):
            return counted_jit(fn, label="query")
        """)) == []
    # not engine-owned: raw jit is fine
    assert _lint_snippet(tmp_path, _D("""\
        __engine_owned__ = False
        import jax

        def build(fn):
            return jax.jit(fn)
        """)) == []


# ------------------------------------------------------------ ZQL002
def test_zql002_fires_on_host_sync_in_hot_path(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        import jax
        import numpy as np
        from repro.launch.trace import hot_path

        @hot_path
        def body(x):
            a = jax.device_get(x)
            b = np.asarray(x)
            c = float(x)
            x.block_until_ready()
            return a, b, c
        """))
    assert [f.rule for f in out] == ["ZQL002"] * 4


def test_zql002_quiet_outside_hot_paths_and_on_constants(tmp_path):
    assert _lint_snippet(tmp_path, OWNED + _D("""\
        import numpy as np
        from repro.launch.trace import hot_path

        def host_side(x):
            return np.asarray(x)            # not a hot path: fine

        @hot_path
        def body(x):
            return x * float(1e-3)          # constant cast: fine
        """)) == []


# ------------------------------------------------------------ ZQL003
def test_zql003_fires_on_order_sensitive_sum_in_estimator(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        import jax.numpy as jnp

        def estimate_view(y, m):
            return jnp.sum(jnp.where(m, y, 0.0))
        """))
    assert _rules(out) == ["ZQL003"]


def test_zql003_quiet_on_chunked_sum_and_exact_counts(tmp_path):
    assert _lint_snippet(tmp_path, OWNED + _D("""\
        import jax.numpy as jnp
        from repro.kernels.segment_stats import canonical_sum

        def estimate_view(y, m):
            n = jnp.sum(m.astype(jnp.int32))     # exact integer count
            return canonical_sum(jnp.where(m, y, 0.0)), n

        def merge_tables(a, b):
            return jnp.sum(a) + jnp.sum(b)       # not an estimator
        """)) == []


# ------------------------------------------------------------ ZQL004
def test_zql004_fires_on_donated_then_reused_local(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        from repro.core.fused import get_fused_ingest

        def step(cols, valid, state, counter, n_batches):
            prog = get_fused_ingest()
            new_state, verdicts = prog(cols, valid, state, counter,
                                       n_batches)
            return new_state, verdicts, state
        """))
    assert _rules(out) == ["ZQL004"]


def test_zql004_fires_on_duplicate_donate_argnums(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        from repro.launch.trace import counted_jit

        def build(fn):
            return counted_jit(fn, donate_argnums=(0, 0))
        """))
    assert _rules(out) == ["ZQL004"]


def test_zql004_quiet_when_donated_state_is_rebound(tmp_path):
    assert _lint_snippet(tmp_path, OWNED + _D("""\
        from repro.core.fused import get_fused_ingest

        def step(cols, valid, state, counter, n_batches):
            prog = get_fused_ingest()
            new_state, verdicts = prog(cols, valid, state, counter,
                                       n_batches)
            state = new_state
            return state, verdicts
        """)) == []


# ------------------------------------------------------------ ZQL005
_PALLAS_RMW = OWNED + _D("""\
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def _merge_kernel(pos_ref, table_ref, vals_ref, out_ref):
        out_ref[...] = table_ref[...]
        out_ref[...] += vals_ref[...]

    def merge(pos, table, vals):
        return pl.pallas_call(
            _merge_kernel,
            out_shape=jax.ShapeDtypeStruct(table.shape, jnp.float32),
            %s
        )(pos, table, vals)
    """)


def test_zql005_fires_on_unaliased_rmw_kernel(tmp_path):
    out = _lint_snippet(tmp_path, _PALLAS_RMW % "interpret=True,")
    assert _rules(out) == ["ZQL005"]


def test_zql005_quiet_when_aliased(tmp_path):
    src = _PALLAS_RMW % "input_output_aliases={1: 0}, interpret=True,"
    assert _lint_snippet(tmp_path, src) == []


# ------------------------------------------------------------ ZQL006
def test_zql006_fires_on_unbucketed_shape_capture(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        import jax.numpy as jnp
        from repro.launch.trace import counted_jit

        def build(batch):
            n = batch.nrows

            def body(cols):
                return jnp.pad(cols, (0, n))

            return counted_jit(body)
        """))
    assert _rules(out) == ["ZQL006"]


def test_zql006_quiet_in_cached_factories(tmp_path):
    assert _lint_snippet(tmp_path, OWNED + _D("""\
        import functools
        import jax.numpy as jnp
        from repro.launch.trace import counted_jit

        @functools.lru_cache(maxsize=8)
        def build(capacity):
            def body(cols):
                return jnp.pad(cols, (0, capacity))

            return counted_jit(body)
        """)) == []


# ------------------------------------------------------------ ZQL007
def test_zql007_fires_on_sync_inside_dispatch_commit_window(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        import jax

        class Engine:
            def ingest(self, cols, valid, state, counter, n_batches):
                prog = self._fused_program(False)
                new_state, verdicts = prog(cols, valid, state, counter,
                                           n_batches)
                f = jax.device_get(verdicts)       # sync before commit
                self._unpack_view_state(new_state)
                return f
        """))
    assert _rules(out) == ["ZQL007"]
    assert out[0].line == 9


def test_zql007_fires_on_device_fetch_and_direct_factory_call(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        from repro.core.fused import get_fused_ingest
        from repro.launch.trace import device_fetch

        class Engine:
            def ingest(self, cols, valid, state, counter, n_batches):
                new_state, verdicts = get_fused_ingest()(
                    cols, valid, state, counter, n_batches)
                f = device_fetch(verdicts)
                self.commit()
                return f
        """))
    assert _rules(out) == ["ZQL007"]


def test_zql007_quiet_when_commit_precedes_the_fetch(tmp_path):
    assert _lint_snippet(tmp_path, OWNED + _D("""\
        import jax

        class Engine:
            def ingest(self, cols, valid, state, counter, n_batches):
                prog = self._fused_program(False)
                new_state, verdicts = prog(cols, valid, state, counter,
                                           n_batches)
                self._unpack_view_state(new_state)  # commit closes window
                return jax.device_get(verdicts)     # lazy verdict: fine

            def report(self, verdicts):
                return jax.device_get(verdicts)     # no open dispatch: fine
        """)) == []


# ------------------------------------------------------------ ZQL008
def test_zql008_fires_on_commit_before_wal_append(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        class Durable:
            def ingest(self, batch):
                rep = self.engine.ingest(batch)     # acked first: WRONG
                self.wal.append_batch(1, batch.columns, batch.valid)
                return rep
        """))
    assert _rules(out) == ["ZQL008"]
    assert out[0].line == 4


def test_zql008_fires_on_version_bump_before_fsync(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        class Durable:
            def commit(self):
                self._state_version += 1            # acked first: WRONG
                self.wal.sync()
        """))
    assert _rules(out) == ["ZQL008"]


def test_zql008_quiet_on_journal_first_and_no_wal(tmp_path):
    # the correct protocol: append/fsync, THEN dispatch/commit
    assert _lint_snippet(tmp_path, OWNED + _D("""\
        class Durable:
            def ingest(self, batch):
                self.wal.append_batch(1, batch.columns, batch.valid)
                return self.engine.ingest(batch)

            def commit(self):
                self.wal.sync()
                out = self.engine.commit()
                self._state_version += 1
                return out

            def checkpoint(self):
                self.wal.sync()
                snap = self.engine.export_canonical()
                self.wal.rotate()                   # bookkeeping, no event
                return snap
        """)) == []
    # functions that never journal are out of scope (the engines
    # themselves bump _state_version freely)
    assert _lint_snippet(tmp_path, OWNED + _D("""\
        class Engine:
            def _post_state_swap(self):
                self._state_version += 1
        """)) == []


# ------------------------------------------------------------ ZQL009
def test_zql009_fires_on_apply_without_verify(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        class Follower:
            def receive(self, records):
                for rec in records:
                    self._apply_one(rec)            # unverified: WRONG
        """))
    assert _rules(out) == ["ZQL009"]
    assert out[0].line == 5


def test_zql009_fires_on_apply_before_verify(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        from repro.core.replication import verify_records

        class Follower:
            def catch_up(self, records):
                self._apply_records(records)        # applied first: WRONG
                verify_records(records, 1, 0)
        """))
    assert _rules(out) == ["ZQL009"]


def test_zql009_quiet_on_verify_then_apply(tmp_path):
    # both verification shapes: the module gate, and a CRC-validating
    # read on a log-named receiver
    assert _lint_snippet(tmp_path, OWNED + _D("""\
        from repro.core.replication import verify_records

        class Follower:
            def catch_up(self, records):
                fresh = verify_records(records, self.epoch, self.seq)
                self._apply_records(fresh)

            def replay(self):
                records, cur = self.wal.read_tail(self.cursor)
                self._apply_records(records)

            def _apply_records(self, records):
                for rec in records:
                    self._apply_one(rec)
        """)) == []
    # non-engine-owned modules are out of scope
    assert _lint_snippet(tmp_path, _D("""\
        def helper(records, engine):
            for rec in records:
                engine._apply_one(rec)
        """)) == []


def test_inline_suppression_drops_the_finding(tmp_path):
    out = _lint_snippet(tmp_path, OWNED + _D("""\
        import jax

        def build(fn):
            return jax.jit(fn)  # zql: ok[ZQL001] fixture exercises raw jit
        """))
    assert out == []


def test_star_suppression_and_select_ignore(tmp_path):
    src = OWNED + _D("""\
        import jax

        def build(fn):
            a = jax.jit(fn)  # zql: ok[*] fixture
            return a, jax.jit(fn)
        """)
    out = _lint_snippet(tmp_path, src)
    assert [f.rule for f in out] == ["ZQL001"] and out[0].line == 6
    assert _lint_snippet(tmp_path, src, select=["ZQL002"]) == []
    assert _lint_snippet(tmp_path, src, ignore=["ZQL001"]) == []


# ------------------------------------------------------------- baseline
def test_baseline_roundtrip_partitions_findings(tmp_path):
    f1 = Finding("a.py", 3, 1, "ZQL001", "m", snippet="x = jax.jit(f)")
    f2 = Finding("b.py", 9, 1, "ZQL002", "m", snippet="y = float(v)")
    base = tmp_path / "base.json"
    write_baseline(base, [f1])
    fps = load_baseline(base)
    assert fps == {f1.fingerprint()}
    new, old = split_baselined([f1, f2], fps)
    assert new == [f2] and old == [f1]
    # fingerprint keys on content, not line number
    moved = Finding("a.py", 77, 1, "ZQL001", "m", snippet="x = jax.jit(f)")
    assert moved.fingerprint() == f1.fingerprint()
    assert load_baseline(tmp_path / "missing.json") == set()


# ------------------------------------------------------------------ CLI
def _cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "contract_check.py"), *args],
        capture_output=True, text=True, cwd=cwd)


def test_cli_repo_is_clean():
    r = _cli()
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout


def test_cli_fails_on_violation_and_baseline_grandfathers(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(OWNED + "import jax\nprog = jax.jit(len)\n")
    r = _cli(str(bad))
    assert r.returncode == 1
    assert "ZQL001" in r.stderr
    base = tmp_path / "base.json"
    r = _cli(str(bad), "--baseline", str(base), "--update-baseline")
    assert r.returncode == 0
    assert json.loads(base.read_text())[0]["rule"] == "ZQL001"
    r = _cli(str(bad), "--baseline", str(base))
    assert r.returncode == 0
    assert "baselined" in r.stdout


def test_cli_select_ignore(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(OWNED + "import jax\nprog = jax.jit(len)\n")
    assert _cli(str(bad), "--select", "ZQL002").returncode == 0
    assert _cli(str(bad), "--ignore", "ZQL001").returncode == 0


# ------------------------------------------------------- path scoping
def test_path_scoping_defaults(tmp_path):
    pkg = tmp_path / "src" / "repro"
    core = pkg / "core"
    launch = pkg / "launch"
    core.mkdir(parents=True)
    launch.mkdir(parents=True)
    bad = "import jax\nprog = jax.jit(len)\n"
    (core / "engine.py").write_text(bad)
    (launch / "driver.py").write_text(bad)
    out = run_lint([tmp_path / "src"], root=tmp_path)
    assert [(f.rule, Path(f.path).name) for f in out] == [
        ("ZQL001", "engine.py")]


# ------------------------------------------------------- jaxpr audit
def test_jaxpr_audit_full_matrix_passes():
    from repro.analysis.jaxpr_audit import run_audit

    results = run_audit()
    assert len(results) == 24, [r.format() for r in results]
    bad = [r.format() for r in results if not r.ok]
    assert not bad, bad
    contracts = {r.contract for r in results}
    assert {"ingest-donation-static", "ingest-1-dispatch",
            "ingest-transfer-clean", "ingest-donation-runtime",
            "query-1-dispatch", "query-transfer-clean",
            "query-cached-0-dispatch", "batch-query-1-dispatch",
            "evict-donation-runtime", "overlap-ingest-0-sync",
            "overlap-committed-buffers-live",
            "overlap-commit-bit-identity"} == contracts
    assert {r.engine for r in results} == {"replicated", "partitioned"}
