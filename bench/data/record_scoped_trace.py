"""Record the small scoped trace that ``test_bench_scopes.py`` reduces,
and the compiled program's HLO text beside it.

  python3 bench/data/record_scoped_trace.py bench/data/v5e_scoped

writes ``v5e_scoped.xplane.pb`` and ``v5e_scoped.hlo.txt``. On the
chip: a measured window (``bench.window``) of 5 steps, each in a
``bench.step`` span, of one jitted program holding a scanned matmul
chain under the scope ``model``, its gradient, and a ``lax.top_k`` over
the gradient under ``demo.topk``.
"""
import glob
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", "src"))
from repro.obs import trace  # noqa: E402

STEPS, LAYERS, D, CHUNK, K = 5, 4, 1024, 4096, 32


def loss(w, x):
    with jax.named_scope(trace.SCOPE_MODEL):
        def layer(h, wl):
            return jnp.tanh(h @ wl), None
        h, _ = jax.lax.scan(layer, x, w)
        return jnp.mean(jnp.square(h.astype(jnp.float32)))


def step(w, x):
    g = jax.grad(loss)(w, x)
    with jax.named_scope(trace.SCOPE_TOPK):
        vals, idx = jax.lax.top_k(jnp.abs(g.reshape(-1, CHUNK)), K)
    return w - 1e-3 * g, vals, idx


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_scoped_trace: no TPU found")
    w = jnp.full((LAYERS, D, D), 0.01, jnp.bfloat16)
    x = jnp.ones((D, D), jnp.bfloat16)
    compiled = jax.jit(step).lower(w, x).compile()
    jax.block_until_ready(compiled(w, x))
    log_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(compiled(w, x))
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    shutil.copy(found[0], out + ".xplane.pb")
    shutil.rmtree(log_dir)
    with open(out + ".hlo.txt", "w") as f:
        f.write(compiled.as_text())
    for ext in (".xplane.pb", ".hlo.txt"):
        print(f"wrote {out}{ext} ({os.path.getsize(out + ext)} bytes)")


if __name__ == "__main__":
    main(sys.argv[1])
