"""Record the small profiler trace that ``test_bench_trace.py`` reduces.

  python3 bench/data/record_trace.py bench/data/v5e_small.xplane.pb

On the chip: a measured window (``bench.window``) of 5 steps, each a
jitted 2048 x 2048 matmul chain in a ``bench.step`` span, separated by
a 20 ms host sleep in a ``bench.feed`` span, so the device idles
mostly while the host feeds.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

STEPS, FEED_S = 5, 0.020


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: no TPU found")
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    log_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("bench.feed"):
                time.sleep(FEED_S)
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    shutil.copy(found[0], out)
    shutil.rmtree(log_dir)
    print(f"wrote {out} ({os.path.getsize(out)} bytes)")


if __name__ == "__main__":
    main(sys.argv[1])
