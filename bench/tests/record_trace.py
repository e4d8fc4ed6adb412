"""Record the small TPU trace ``data/small.xplane.pb`` that the trace
reduction is tested on.  Run on a machine with a TPU:

    python3 bench/tests/record_trace.py <out.xplane.pb>

Three calls of one jitted program, each inside a ``bench.ingest_log`` span
and followed by a ``bench.query`` span that waits 20 ms on the host, so the
trace holds device work, host spans and idle gaps between them.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, xplane  # noqa: E402


@jax.jit
def step(x):
    return jnp.tanh(x @ x) + 1.0


def main(out: str) -> None:
    x = jnp.ones((2048, 2048), jnp.float32)
    step(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(
            d, profiler_options=harness._profile_options())
        for _ in range(3):
            with TraceAnnotation("bench.ingest_log"):
                x = step(x)
            with TraceAnnotation("bench.query"):
                x.block_until_ready()
                time.sleep(0.02)
        jax.profiler.stop_trace()
        shutil.copy(xplane.find_xplane(Path(d)), out)
    print(xplane.reduce_file(Path(out)))


if __name__ == "__main__":
    main(sys.argv[1])
