"""Record the small chip trace that ``tests/test_trace.py`` reads:
a named matmul program run a few times with host pauses between."""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main():
    out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/recorded.xplane.pb"

    def recorded_step(x):
        with jax.named_scope("recorded_matmul"):
            y = jnp.dot(x, x, preferred_element_type=jnp.float32)
        return jnp.tanh(y).astype(x.dtype)

    step = jax.jit(recorded_step)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()
    d = tempfile.mkdtemp(prefix="recorded_trace")     # under TMPDIR
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(4):
            step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench/host_pause"):
                time.sleep(0.003)
    jax.profiler.stop_trace()
    path = glob.glob(d + "/plugins/profile/*/*.xplane.pb")[0]
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(d, ignore_errors=True)
    print(out, os.path.getsize(out), jax.devices()[0].platform)


if __name__ == "__main__":
    main()
