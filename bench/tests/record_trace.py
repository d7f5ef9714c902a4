#!/usr/bin/env python3
"""Records the small loop trace that ``test_xtrace_chip.py`` reads.

    python3 bench/tests/record_trace.py <out_dir>

On one TPU: a jitted ``fori_loop`` (one ``while`` on the device) of
``STEPS`` matmul steps, each of which then waits ``STALL_S`` on a host
callback, profiled once after the clock marker and inside a
``bench/step`` annotation that carries the host clock.  The
``.xplane.pb`` lands under ``out_dir``; copy it to
``bench/tests/data/trace_loop_v5e.xplane.pb``.
"""
import sys
import time
from pathlib import Path

STEPS = 4
STALL_S = 0.005


def main(out: str) -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import io_callback

    from bench.xtrace import MARKER

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2

    def stall(i):
        time.sleep(STALL_S)
        return np.zeros((), np.float32)

    def body(i, x):
        x = jnp.tanh(x @ x)
        z = io_callback(stall, jax.ShapeDtypeStruct((), jnp.float32), i)
        return x + z

    f = jax.jit(lambda x: jax.lax.fori_loop(0, STEPS, body, x))
    x = jnp.full((1024, 1024), 0.01, jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation(MARKER, t=time.perf_counter()):
        pass
    with jax.profiler.TraceAnnotation("bench/step", t=time.perf_counter()):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
