"""Record the small TPU trace that ``test_chipbench_trace.py`` reduces.

    python tests/chipbench/record_trace.py    # on a TPU; writes
                                               # chiprun_out/small.xplane.pb

Two small programs run under the benchmark's host spans inside one
``window`` span: ``step`` (a sort and a scatter) under
``journal_dispatch``, ``probe`` under ``wave_step``, with host-only
spans (``batch_build``, ``commit``, ``generator_sleep``) between them.
"""
import pathlib
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import tracing  # noqa: E402


@jax.jit
def step(x, k):
    s = jnp.sort(x)
    return jnp.zeros_like(x).at[k].add(s) + jnp.cumsum(s)


@jax.jit
def probe(x):
    return jnp.sum(jnp.sort(x * 3.0)[::7])


def main() -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record this trace on a TPU")
    x = jax.random.normal(jax.random.key(0), (1 << 16,))
    k = jax.random.randint(jax.random.key(1), (1 << 16,), 0, 1 << 16)
    xs = [x + i for i in range(3)]
    step(x, k).block_until_ready()
    probe(x).block_until_ready()
    span = tracing.Spans(enabled=True)
    with tempfile.TemporaryDirectory() as d:
        with tracing.recording(d):
            with span(tracing.WINDOW):
                for i in range(3):
                    with span("batch_build"):
                        time.sleep(0.002)
                    with span("journal_dispatch"):
                        step(xs[i], k).block_until_ready()
                    with span("commit"):
                        time.sleep(0.003)
                    with span("wave_step"):
                        probe(xs[i]).block_until_ready()
                    with span("generator_sleep"):
                        time.sleep(0.004)
        out = pathlib.Path("chiprun_out")
        out.mkdir(exist_ok=True)
        shutil.copy(tracing.trace_file(d), out / "small.xplane.pb")


if __name__ == "__main__":
    main()
