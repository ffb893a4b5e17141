"""Record the small TPU trace that ``test_trace.py`` reads.

    python3 chipbench/tests/data/record_trace.py <out_dir>

Two requests of two matmuls each, wrapped in the harness's host spans,
with host sleeps between and inside them, so the trace has device ops,
idle gaps inside a request and between requests.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", ".."))
from chipbench.harness import BETWEEN, REQUEST  # noqa: E402
from chipbench.trace import SLICE  # noqa: E402


def main() -> None:
    out = sys.argv[1]
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(out + "/raw")
    with jax.profiler.TraceAnnotation(SLICE):
        for _ in range(2):
            with jax.profiler.TraceAnnotation(REQUEST):
                f(x).block_until_ready()
                time.sleep(0.002)
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation(BETWEEN):
                time.sleep(0.003)
    jax.profiler.stop_trace()
    path, = glob.glob(out + "/raw/**/*.xplane.pb", recursive=True)
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(out + "/raw")


if __name__ == "__main__":
    main()
