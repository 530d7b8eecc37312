"""Causal data curation — ZaliQL as a first-class training-framework
feature (the integration story from DESIGN.md §3).

Question every pretraining team asks: "does data property T *cause* better
(lower) loss, or is it just correlated through confounders?" Here the
training pipeline emits per-example telemetry and the causal engine answers
with CEM/ATE instead of a correlational dashboard.

Setup (synthetic but structurally honest):
  * examples have a data property T ("curated source") whose TRUE causal
    effect on loss is a planted -0.30;
  * a confounder (document length) affects BOTH curation probability and
    loss, making the naive correlation wildly optimistic;
  * we train a tiny LM, collect per-example loss telemetry, and compare
    naive difference-in-means vs CEM ATE against the planted truth.

Run:  PYTHONPATH=src python examples/causal_data_curation.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (CoarsenSpec, cem, difference_in_means, estimate_ate)
from repro.data.columnar import Table
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import PRESETS
from repro.models import forward, init_params

TRUE_EFFECT = -0.30


def make_corpus(rng, n_docs, seq, vocab):
    """Docs with a 'length' confounder: long docs are more regular (lower
    loss) AND more likely curated. Curation itself adds extra regularity
    worth TRUE_EFFECT nats."""
    length = rng.uniform(0, 1, n_docs)                      # confounder
    curated = (rng.random(n_docs) < 0.15 + 0.7 * length).astype(np.int32)
    # regularity in [0, 1]: longer docs more regular; curation adds more
    regular = np.clip(0.25 + 0.5 * length + 0.25 * curated
                      + rng.normal(0, 0.05, n_docs), 0, 1)
    toks = rng.integers(0, vocab, (n_docs, seq), dtype=np.int64)
    period = rng.integers(2, 6, (n_docs, 1))
    pattern = (np.arange(seq)[None, :] // period) % vocab
    use = rng.random((n_docs, seq)) < regular[:, None]
    tokens = np.where(use, pattern, toks).astype(np.int32)
    return tokens, curated, length


def main():
    rng = np.random.default_rng(0)
    cfg = PRESETS["lm-tiny"]
    n_docs, seq = 4096, 64
    tokens, curated, length = make_corpus(rng, n_docs, seq, cfg.vocab_size)

    print("== scoring per-example loss with the LM (telemetry pass) ==")
    params = init_params(jax.random.PRNGKey(0), cfg)

    @jax.jit
    def example_loss(params, toks):
        logits, _, _ = forward(params, cfg, {"tokens": toks})
        labels = jnp.roll(toks, -1, axis=1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        return jnp.mean(nll[:, :-1], axis=1)

    losses = []
    bs = 256
    for i in range(0, n_docs, bs):
        losses.append(np.asarray(example_loss(
            params, jnp.asarray(tokens[i:i + bs]))))
    loss = np.concatenate(losses)
    # normalize loss scale so the planted effect is in nats as stated
    loss = (loss - loss.mean()) / max(loss.std(), 1e-9)
    # planted structural equation for the telemetry outcome:
    loss = (-1.2 * length + TRUE_EFFECT * curated
            + 0.15 * rng.normal(0, 1, n_docs) + loss * 0.05)

    table = Table.from_numpy({
        "curated": curated, "length": length.astype(np.float32),
        "loss": loss.astype(np.float32)})

    naive = float(difference_in_means(table["loss"], table["curated"],
                                      table.valid))
    res = cem(table, "curated", "loss",
              {"length": CoarsenSpec.equal_width(0, 1, 20)})
    est = estimate_ate(res.groups, table["loss"], table["curated"],
                       res.table.valid)
    print(f"naive effect of curation on loss : {naive:+.3f}  "
          "(confounded by doc length)")
    print(f"CEM ATE                          : {float(est.ate):+.3f}  "
          f"[truth {TRUE_EFFECT:+.3f}]")
    print(f"matched {int(est.n_matched_treated)} curated vs "
          f"{int(est.n_matched_control)} uncurated docs in "
          f"{int(est.n_groups)} length strata")
    assert abs(float(est.ate) - TRUE_EFFECT) < abs(naive - TRUE_EFFECT), \
        "CEM should beat the naive estimate"
    assert abs(float(est.ate) - TRUE_EFFECT) < 0.1
    print("OK — curation effect recovered causally")


if __name__ == "__main__":
    enable_compile_cache()
    main()
