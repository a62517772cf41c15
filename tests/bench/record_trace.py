"""Record the small TPU trace that ``test_trace_reduce.py`` reads.

    chiprun -- python tests/bench/record_trace.py chiprun_out/small.xplane.pb

Two small device programs inside a ``bench.window`` annotation, with
host sleeps between them, so that the trace has device ops, idle gaps
and annotations to attribute them to.
"""

import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    sort = jax.jit(lambda x: jnp.sort(x))
    total = jax.jit(lambda x: (x * 2).sum())
    x = jnp.arange(1 << 20, dtype=jnp.int32)[::-1]
    sort(x).block_until_ready()
    total(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.submit"):
            sort(x).block_until_ready()
        time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.result"):
            total(x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    shutil.copy(sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))[-1], out)
    shutil.rmtree(d)


if __name__ == "__main__":
    main(sys.argv[1])
