"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU): shape and
dtype sweeps per kernel."""
import functools
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.demo import dct
from repro.kernels import ops, ref, topk_kernel

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.parametrize("nc", [1, 5, 128, 300])
@pytest.mark.parametrize("s", [8, 16, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dct2_kernel_matches_ref(nc, s, dtype):
    x = jax.random.normal(jax.random.PRNGKey(nc + s), (nc, s, s)).astype(dtype)
    a = ops.dct2_chunks(x, interpret=True)
    b = ref.dct2_chunks(x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("nc,s", [(7, 16), (64, 8), (130, 16)])
def test_idct2_kernel_roundtrip(nc, s):
    x = jax.random.normal(jax.random.PRNGKey(0), (nc, s, s))
    np.testing.assert_allclose(np.asarray(ops.idct2_chunks(
        ops.dct2_chunks(x, interpret=True), interpret=True)),
                               np.asarray(x), atol=1e-5)


def _tied_rows(key, nc, e):
    """Magnitudes on a grid of halves (many ties), and each odd column
    the negation of the even one before it (+x, -x pairs)."""
    x = jnp.round(jax.random.normal(key, (nc, e)) * 2) / 2
    return x.at[:, 1::2].set(-x[:, 0::2])


def _zero_rows(key, nc, e):
    """All-zero chunks, as in step 1 (embedding rows of tokens not in
    the batch), half of them -0.0, beside rows with a few nonzeros."""
    x = jnp.where(jax.random.uniform(key, (nc, e)) < 0.002,
                  jax.random.normal(key, (nc, e)), 0.0)
    x = x.at[::3].set(0.0)
    return x.at[1::3].set(-0.0)


_ROWS = {"normal": lambda key, nc, e: jax.random.normal(key, (nc, e)),
         "ties": _tied_rows, "zeros": _zero_rows}
_TOPK_CASES = [
    pytest.param(nc, e, k, "normal", topk_kernel.DEFAULT_BLOCK_ROWS,
                 id=f"{k}-{e}-{nc}")
    for k in (1, 8, 32) for e in (64, 256, 4096) for nc in (1, 50, 300)
] + [
    pytest.param(nc, e, 32, rows, br, id=f"{rows}-{nc}x{e}-block{br}")
    for rows in ("ties", "zeros")
    for nc, e, br in ((300, 4096, 64), (70, 256, 16))
] + [pytest.param(70, 4096, 32, "normal", 16, id="normal-70x4096-block16")]


@pytest.mark.parametrize("nc,e,k,rows,block_rows", _TOPK_CASES)
def test_topk_kernel_matches_ref(nc, e, k, rows, block_rows):
    """Exactly ``lax.top_k`` of the magnitudes and the signed values it
    picks: the same indices in the same order (lower index first on
    ties), the same value bits (the sign of a zero too), also where the
    last block runs past the rows."""
    x = _ROWS[rows](jax.random.PRNGKey(nc + e + k), nc, e)
    v1, i1 = ops.topk_chunks(x, k, interpret=True, block_rows=block_rows)
    v2, i2 = ref.topk_chunks(x, k)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(v1).view(np.int32),
                                  np.asarray(v2).view(np.int32))


def test_topk_kernel_ties_stable():
    x = jnp.asarray([[2.0, -2.0, 1.0, 1.0]])
    v1, i1 = ops.topk_chunks(x, 3, interpret=True)
    v2, i2 = ref.topk_chunks(x, 3)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_topk_kernel_batched_matches_unbatched():
    """The validator's replay audit vmaps the peer's step over the
    audited peers: each row of the batched kernel must give, bit for
    bit, what the peer's own unbatched call gives, or an honest peer's
    payload would not match its replay."""
    x = _tied_rows(jax.random.PRNGKey(3), 3 * 70, 256).reshape(3, 70, 256)
    select = functools.partial(ops.topk_chunks, k=8, interpret=True,
                               block_rows=16)
    vb, ib = jax.vmap(select)(x)
    for p in range(3):
        v, i = select(x[p])
        np.testing.assert_array_equal(np.asarray(ib[p]), np.asarray(i))
        np.testing.assert_array_equal(np.asarray(vb[p]).view(np.int32),
                                      np.asarray(v).view(np.int32))


def _instructions(compiled):
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in compiled.as_text().splitlines()
            if re.match(r"\s*(ROOT\s+)?%|ENTRY", line)]


def test_topk_compress_on_cpu_is_the_sorting_path():
    """Off the TPU, ``topk_compress`` compiles to the instructions of
    ``lax.top_k`` and ``take_along_axis`` it always had, and gives the
    same payload bit for bit."""
    from repro.schemes import demo

    def sorting(coeffs):
        _, idx = jax.lax.top_k(jnp.abs(coeffs), 8)
        return (jnp.take_along_axis(coeffs, idx, axis=-1),
                idx.astype(jnp.int32))

    def compress(coeffs):
        return tuple(demo.topk_compress(coeffs, 8))

    x = _tied_rows(jax.random.PRNGKey(5), 50, 256)
    want, got = jax.jit(sorting), jax.jit(compress)
    assert (_instructions(got.lower(x).compile())
            == _instructions(want.lower(x).compile()))
    for a, b in zip(got(x), want(x)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a).view(np.int32),
                                      np.asarray(b).view(np.int32))


_PER_SHARD = textwrap.dedent("""
    import functools, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro import hints
    from repro.kernels import ref, topk_kernel
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=2, model=2)
    select = functools.partial(topk_kernel.topk_chunks, k=8,
                               interpret=True, block_rows=16)

    def per_peer(xs):
        v, i = hints.per_chunk_shard(select, xs[0])
        return v[None], i[None]

    step = jax.jit(jax.shard_map(
        per_peer, in_specs=P("data"), out_specs=P("data"),
        axis_names={"data"}, check_vma=False))
    for rows in (48, 47):                # split over "model"; whole
        x = jax.random.normal(jax.random.PRNGKey(rows), (2, rows, 256))
        with jax.set_mesh(mesh):
            # the peer's shard_map and the kernel's inside it
            assert str(jax.make_jaxpr(step)(x)).count("shard_map[") == 2
            v1, i1 = map(np.asarray, step(x))
        for p in range(2):
            v2, i2 = ref.topk_chunks(x[p], 8)
            np.testing.assert_array_equal(i1[p], np.asarray(i2))
            np.testing.assert_array_equal(v1[p], np.asarray(v2))
    print("OK")
""")


def test_topk_kernel_runs_per_chunk_shard():
    """Inside a peer ``shard_map`` on a (data, model) mesh of four
    devices, ``hints.per_chunk_shard`` runs the kernel with every axis
    manual, the chunk rows split over the model axis where they divide
    evenly and whole where they do not, and equals the sort each way."""
    out = subprocess.run(
        [sys.executable, "-c", _PER_SHARD], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": SRC}, timeout=600)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-3000:]


@pytest.mark.parametrize("shape", [(100,), (128, 64), (13, 7, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("beta", [0.0, 0.9, 0.999])
def test_ef_update_kernel(shape, dtype, beta):
    e = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), shape).astype(dtype)
    a = ops.ef_update(e, g, beta, interpret=True)
    b = ref.ef_update(e, g, beta)
    assert a.dtype == e.dtype and a.shape == e.shape
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-6)


def test_demo_encode_decode_match_reference_pipeline():
    m = dct.chunk_meta((100, 70), 16)
    x = jax.random.normal(jax.random.PRNGKey(2), (100, 70))
    np.testing.assert_allclose(
        np.asarray(ops.demo_encode(x, m, interpret=True)),
        np.asarray(dct.encode(x, m)), atol=1e-5)
    c = dct.encode(x, m)
    np.testing.assert_allclose(
        np.asarray(ops.demo_decode(c, m, interpret=True)),
        np.asarray(dct.decode(c, m)), atol=1e-5)


def test_kernel_backed_local_step_equals_ref():
    """Swapping encode_fn to the Pallas pipeline changes nothing."""
    from repro.schemes import demo as compress
    from repro.schemes import demo as optimizer
    params = {"w": jax.random.normal(jax.random.PRNGKey(3), (64, 48))}
    grads = {"w": jax.random.normal(jax.random.PRNGKey(4), (64, 48))}
    metas = compress.tree_meta(params, 16)
    ef = jax.tree.map(jnp.zeros_like, params)
    p_ref, _ = optimizer.local_step(grads, ef, beta=0.9, chunk=16, k=8,
                                    metas=metas)
    p_k, _ = optimizer.local_step(grads, ef, beta=0.9, chunk=16, k=8,
                                  metas=metas,
                                  encode_fn=functools.partial(
                                      ops.demo_encode, interpret=True))
    np.testing.assert_allclose(np.asarray(p_ref["w"].vals),
                               np.asarray(p_k["w"].vals), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(p_ref["w"].idx),
                                  np.asarray(p_k["w"].idx))
