"""Quickstart: causal inference with ZaliQL-on-JAX in ~40 lines.

Estimates the causal effect of a binary treatment under confounding, shows
why the naive correlational estimate is wrong, and prints balance
diagnostics — the paper's core loop (CEM -> overlap filter -> Eq. 4 ATE).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import (CoarsenSpec, awmd, cem, difference_in_means,
                        estimate_ate, raw_imbalance)
from repro.data.columnar import Table
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

# --- observational data with a confounder -------------------------------
rng = np.random.default_rng(0)
n = 50_000
severity = rng.normal(0, 1, n)                  # confounder (e.g. illness)
treated = (rng.random(n) < 1 / (1 + np.exp(-1.5 * severity))).astype(np.int32)
# true effect of treatment: -2.0 (helps); severity hurts (+3.0)
outcome = (-2.0 * treated + 3.0 * severity + rng.normal(0, .5, n)
           ).astype(np.float32)

table = Table.from_numpy({"severity": severity.astype(np.float32),
                          "t": treated, "y": outcome})

# --- naive (predictive) answer: wrong sign! ------------------------------
naive = float(difference_in_means(table["y"], table["t"], table.valid))
print(f"naive difference-in-means : {naive:+.3f}   (sicker people get "
      "treated, so treatment looks harmful)")

# --- ZaliQL: coarsened exact matching + ATE ------------------------------
res = cem(table, "t", "y",
          specs={"severity": CoarsenSpec.equal_width(-4, 4, 32)})
est = estimate_ate(res.groups, table["y"], table["t"], res.table.valid)
print(f"CEM ATE                   : {float(est.ate):+.3f} "
      f"(+- {float(est.variance) ** 0.5:.3f})   [truth: -2.000]")
print(f"matched: {int(est.n_matched_treated)} treated / "
      f"{int(est.n_matched_control)} control in {int(est.n_groups)} groups")

# --- balance diagnostics (paper Eq. 5) -----------------------------------
raw = raw_imbalance({"severity": table["severity"]}, table["t"], table.valid)
bal = awmd(res.groups, {"severity": table["severity"]}, table["t"],
           res.table.valid)
print(f"severity imbalance        : raw {float(raw['severity']):.3f} -> "
      f"matched {float(bal['severity']):.3f}")

assert abs(float(est.ate) + 2.0) < 0.15, "ATE recovery failed"
print("OK")
