"""Record the small profiler trace that the trace-reduction test reads.

    python3 chipbench/testdata/record_trace.py <out_dir>

On the chip: five steps of a small jitted program inside the harness's
own annotations (``window``, ``step``, ``device_step``), with a 20 ms
host pause (``plan``) before each, so the trace holds device ops, idle
gaps and the host spans that label them.  Copy the ``.xplane.pb`` it
writes to ``chipbench/testdata/small.xplane.pb``.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    step = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("step"):
                with jax.profiler.TraceAnnotation("plan"):
                    time.sleep(0.02)
                with jax.profiler.TraceAnnotation("device_step"):
                    y = step(x)
                y.block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
