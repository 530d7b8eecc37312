"""ZQL003 — order-sensitive reductions in estimator bodies.

Contract (``docs/architecture.md`` — the bit-identity contract): the
float reductions that produce an estimate must be a deterministic
function of the canonical group content alone — invariant to capacity,
partition count and mesh size. A bare ``jnp.sum`` over a
capacity-dependent axis re-associates when the capacity grows and
``jax.lax.psum`` re-associates with the device count, so estimator
bodies must route cross-group float reductions through
``kernels.segment_stats.canonical_sum`` (pairwise fold whose
association is fixed in the program).

Scope: functions whose name contains ``estimate`` in engine-owned
modules — the canonical estimator bodies. Integer/bool count reductions
are exact in fp32/int32 and exempt (detected via ``.astype(int*)`` on
the reduced operand).
"""
from __future__ import annotations

import ast
from typing import Iterator

from repro.analysis.lint import Finding, ModuleContext
from repro.analysis.rules import _common

_ORDER_SENSITIVE = ("jax.numpy.sum", "jax.numpy.nansum", "jax.lax.psum",
                    "numpy.sum")
_EXACT_DTYPES = ("int32", "int64", "uint32", "uint64", "bool_", "int8",
                 "uint8", "int16", "uint16")


def _is_exact_count(call: ast.Call, aliases) -> bool:
    """True when the reduced operand is integer-cast (exact sums)."""
    for arg in call.args[:1]:
        for sub in ast.walk(arg):
            if (isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr == "astype"):
                for a in sub.args:
                    canon = _common.canonical(a, aliases) or ""
                    if canon.split(".")[-1] in _EXACT_DTYPES:
                        return True
    for kw in call.keywords:
        if kw.arg == "dtype":
            canon = _common.canonical(kw.value, aliases) or ""
            if canon.split(".")[-1] in _EXACT_DTYPES:
                return True
    return False


class Rule:
    id = "ZQL003"
    summary = ("order-sensitive reduction in an estimator body "
               "(use kernels.segment_stats.canonical_sum)")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if not ctx.engine_owned:
            return
        aliases = _common.import_aliases(ctx.tree)
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, ast.FunctionDef):
                continue
            if "estimate" not in fn.name:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                canon = _common.call_canonical(node, aliases)
                if canon not in _ORDER_SENSITIVE:
                    continue
                if _is_exact_count(node, aliases):
                    continue
                yield ctx.finding(
                    node, self.id,
                    f"`{canon}` in estimator body `{fn.name}` — "
                    "order-sensitive float reduction breaks the "
                    "bit-identity contract; route through "
                    "kernels.segment_stats.canonical_sum (or inject via "
                    "sum_fn=)")


RULE = Rule()
