"""Record the small TPU trace that ``test_xplane.py`` reads: inside a
``bench.window`` span, three ``bench.step`` spans, each a jitted matmul, a
20 ms sleep inside a ``bench.sleep`` span and a jitted add, with 10 ms
outside any step after each.

    python3 bench/tests/record_trace.py bench/tests/data    # on the chip
"""

import glob
import pathlib
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out: pathlib.Path) -> None:
    out.mkdir(parents=True, exist_ok=True)

    @jax.jit
    def mm(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def add(x):
        return x * 2.0 + 1.0

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    mm(x).block_until_ready()
    add(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = out / "_trace"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                mm(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    time.sleep(0.02)
                add(x).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    found = sorted(glob.glob(str(tmp / "plugins" / "profile" / "*" / "*.xplane.pb")))
    shutil.copy(found[-1], out / "small.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(pathlib.Path(sys.argv[1]))
