"""E6: Pallas kernel microbench — kernel (interpret mode on CPU) vs the
pure-jnp reference oracle, at the paper's compression shapes.

The kernels run compiled on a TPU and through the Pallas interpreter on
the CPU; every row names the platform. Interpret-mode timings are not TPU
performance — off the TPU the deliverable is (a) correctness at
benchmark shapes, (b) the jnp-ref wall time (the actual CPU fast path),
(c) FLOP counts per call for the roofline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.demo import dct as dct_ref
from repro.kernels import ops, ref


def run():
    platform = jax.devices()[0].platform
    if platform not in ("cpu", "tpu"):
        raise SystemExit(f"kernel_bench runs on cpu or tpu, not {platform}")
    interpret = platform != "tpu"
    key = jax.random.PRNGKey(0)
    rows = []
    for nc, s in [(64, 64), (256, 64), (64, 32)]:
        x = jax.random.normal(key, (nc, s, s), jnp.float32)
        ref_t = common.time_call(
            jax.jit(ref.dct2_chunks), x, repeat=5)
        out_k = ops.dct2_chunks(x, interpret=interpret)
        out_r = ref.dct2_chunks(x)
        err = float(jnp.max(jnp.abs(out_k - out_r)))
        # round-trip through the kernel pair
        back = ops.idct2_chunks(out_k, interpret=interpret)
        rt = float(jnp.max(jnp.abs(back - x)))
        flops = 2 * 2 * nc * s * s * s   # two s x s matmuls per chunk
        rows.append({"platform": platform,
                     "kernel": "dct2+idct2", "shape": f"{nc}x{s}x{s}",
                     "jnp_ref_us": ref_t, "max_err_vs_ref": err,
                     "roundtrip_err": rt, "mflops_per_call": flops / 1e6})
        assert err < 1e-4 and rt < 1e-4

    for nc, n, k in [(256, 4096, 32), (64, 1024, 8)]:
        x = jax.random.normal(key, (nc, n), jnp.float32)
        v_k, i_k = ops.topk_chunks(x, k, interpret=interpret)
        v_r, i_r = ref.topk_chunks(x, k)
        # compare as sets per row (ties may order differently)
        sk = np.sort(np.abs(np.asarray(v_k)), axis=-1)
        sr = np.sort(np.abs(np.asarray(v_r)), axis=-1)
        err = float(np.max(np.abs(sk - sr)))
        ref_t = common.time_call(
            jax.jit(lambda a: ref.topk_chunks(a, k)), x, repeat=5)
        rows.append({"platform": platform,
                     "kernel": "topk", "shape": f"{nc}x{n} k={k}",
                     "jnp_ref_us": ref_t, "max_err_vs_ref": err,
                     "roundtrip_err": 0.0,
                     "mflops_per_call": nc * n / 1e6})
        assert err < 1e-5

    for shape in [(1024, 1024), (4096, 512)]:
        e = jax.random.normal(key, shape, jnp.float32)
        g = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32)
        out_k = ops.ef_update(e, g, 0.999, interpret=interpret)
        out_r = ref.ef_update(e, g, 0.999)
        err = float(jnp.max(jnp.abs(out_k - out_r)))
        ref_t = common.time_call(
            jax.jit(lambda a, b: ref.ef_update(a, b, 0.999)), e, g,
            repeat=5)
        rows.append({"platform": platform,
                     "kernel": "ef_update", "shape": str(shape),
                     "jnp_ref_us": ref_t, "max_err_vs_ref": err,
                     "roundtrip_err": 0.0,
                     "mflops_per_call": 2 * e.size / 1e6})
        assert err < 1e-5

    for bh, t, n, L in [(4, 256, 64, 64), (2, 512, 64, 64)]:
        ks = jax.random.split(key, 4)
        r = jax.random.normal(ks[0], (bh, t, n))
        kk = jax.random.normal(ks[1], (bh, t, n))
        v = jax.random.normal(ks[2], (bh, t, n))
        lw = -jnp.exp(jax.random.normal(ks[3], (bh, t, n)) - 1.0)
        u = 0.5 * jnp.ones((n,))
        o_k, s_k = ops.wkv_chunks(r, kk, v, lw, u, chunk=L,
                                  interpret=interpret)
        o_r, s_r = ref.wkv_chunks(r, kk, v, lw, u, chunk=L)
        err = float(jnp.max(jnp.abs(o_k - o_r)))
        ref_t = common.time_call(
            jax.jit(lambda *a: ref.wkv_chunks(*a, chunk=L)),
            r, kk, v, lw, u, repeat=3)
        # intra scores + inter state per chunk
        flops = bh * t * (2 * L * n + 4 * n * n)
        rows.append({"platform": platform,
                     "kernel": "wkv_fused", "shape": f"{bh}x{t}x{n} L={L}",
                     "jnp_ref_us": ref_t, "max_err_vs_ref": err,
                     "roundtrip_err": 0.0, "mflops_per_call": flops / 1e6})
        assert err < 1e-3

    common.emit("kernel_bench", rows,
                ["platform", "kernel", "shape", "jnp_ref_us",
                 "max_err_vs_ref", "roundtrip_err", "mflops_per_call"])
    return rows


if __name__ == "__main__":
    run()
