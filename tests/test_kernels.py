"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU): shape and
dtype sweeps per kernel."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.demo import dct
from repro.kernels import ops, ref


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


@pytest.mark.parametrize("nc", [1, 50, 300])
@pytest.mark.parametrize("e", [64, 256, 4096])
@pytest.mark.parametrize("k", [1, 8, 32])
def test_topk_kernel_matches_ref(nc, e, k):
    x = jax.random.normal(jax.random.PRNGKey(nc + e + k), (nc, e))
    v1, i1 = ops.topk_chunks(x, k, interpret=True)
    v2, i2 = ref.topk_chunks(x, k)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-6)


def test_topk_kernel_ties_stable():
    x = jnp.asarray([[2.0, -2.0, 1.0, 1.0]])
    v1, i1 = ops.topk_chunks(x, 3, interpret=True)
    v2, i2 = ref.topk_chunks(x, 3)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


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
    st1 = optimizer.init_state(params)
    p_ref, s_ref = optimizer.local_step(grads, st1, beta=0.9, chunk=16, k=8,
                                        metas=metas)
    st2 = optimizer.init_state(params)
    p_k, s_k = optimizer.local_step(grads, st2, beta=0.9, chunk=16, k=8,
                                    metas=metas,
                                    encode_fn=functools.partial(
                                        ops.demo_encode, interpret=True))
    np.testing.assert_allclose(np.asarray(p_ref["w"].vals),
                               np.asarray(p_k["w"].vals), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(p_ref["w"].idx),
                                  np.asarray(p_k["w"].idx))
