"""DeMo optimizer invariants (Algo 2)."""
import jax
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, st

from repro.demo import dct
from repro.schemes import apply_update
from repro.schemes import demo as compress
from repro.schemes import demo as optimizer
from repro.schemes.demo import Payload


def _zeros(params):
    return jax.tree.map(jnp.zeros_like, params)


def _setup(key=0, shape=(64, 48), chunk=16):
    k = jax.random.PRNGKey(key)
    params = {"w": jax.random.normal(k, shape)}
    grads = {"w": jax.random.normal(jax.random.fold_in(k, 1), shape)}
    metas = compress.tree_meta(params, chunk)
    return params, grads, metas


def test_error_feedback_conservation():
    """After one step from zero EF: e_new = g - decode(payload)."""
    params, grads, metas = _setup()
    payloads, ef = optimizer.local_step(grads, _zeros(params), beta=0.9,
                                        chunk=16, k=8, metas=metas)
    z = compress.decompress_tree(payloads, metas)
    np.testing.assert_allclose(np.asarray(ef["w"]),
                               np.asarray(grads["w"] - z["w"]), atol=1e-5)


def test_ef_accumulates_with_beta():
    params, grads, metas = _setup()
    ef = {"w": jnp.ones_like(params["w"])}
    payloads, ef = optimizer.local_step(grads, ef, beta=0.5, chunk=16,
                                        k=8, metas=metas)
    z = compress.decompress_tree(payloads, metas)
    expect = 0.5 * 1.0 + grads["w"] - z["w"]
    np.testing.assert_allclose(np.asarray(ef["w"]), np.asarray(expect),
                               atol=1e-5)


def test_aggregate_is_signed():
    params, grads, metas = _setup()
    p1, _ = optimizer.local_step(grads, _zeros(params), beta=0.9,
                                 chunk=16, k=8, metas=metas)
    delta = optimizer.aggregate([p1, p1], metas)
    vals = np.unique(np.asarray(delta["w"]))
    assert set(vals).issubset({-1.0, 0.0, 1.0})


def test_normalization_neutralizes_rescaling():
    """Byzantine defense (§4): scaling one peer's payload by 1e6 changes
    nothing after DCT-domain normalization."""
    params, grads, metas = _setup()
    p1, _ = optimizer.local_step(grads, _zeros(params), beta=0.9,
                                 chunk=16, k=8, metas=metas)
    p_scaled = jax.tree.map(
        lambda p: Payload(vals=p.vals * 1e6, idx=p.idx), p1,
        is_leaf=lambda x: isinstance(x, Payload))
    d1 = optimizer.aggregate([p1, p1], metas)
    d2 = optimizer.aggregate([p1, p_scaled], metas)
    np.testing.assert_array_equal(np.asarray(d1["w"]), np.asarray(d2["w"]))


def test_without_normalization_rescaling_dominates():
    params, grads, metas = _setup()
    p1, _ = optimizer.local_step(grads, _zeros(params), beta=0.9,
                                 chunk=16, k=8, metas=metas)
    p_neg = jax.tree.map(lambda p: Payload(vals=-1e6 * p.vals, idx=p.idx),
                         p1, is_leaf=lambda x: isinstance(x, Payload))
    d = optimizer.aggregate([p1, p_neg], metas, normalize=False)
    d_honest = optimizer.aggregate([p1], metas, normalize=False)
    # attacker flips nearly every sign
    flip = np.mean(np.asarray(d["w"]) == -np.asarray(d_honest["w"]))
    assert flip > 0.9


def test_apply_update_moves_by_lr():
    params = {"w": jnp.zeros((8, 8))}
    delta = {"w": jnp.ones((8, 8))}
    out = apply_update(params, delta, lr=0.1)
    np.testing.assert_allclose(np.asarray(out["w"]), -0.1, atol=1e-7)


@settings(max_examples=8, deadline=None)
@given(k=st.integers(1, 16), beta=st.floats(0.0, 0.999))
def test_compression_residual_shrinks_with_k(k, beta):
    """Larger k ⇒ decode(payload) closer to the EF buffer."""
    params, grads, metas = _setup(key=k)
    p_small, _ = optimizer.local_step(grads, _zeros(params), beta=beta,
                                      chunk=16, k=k, metas=metas)
    p_big, _ = optimizer.local_step(grads, _zeros(params), beta=beta,
                                    chunk=16, k=min(16 * 16, k * 2),
                                    metas=metas)
    z_s = compress.decompress_tree(p_small, metas)["w"]
    z_b = compress.decompress_tree(p_big, metas)["w"]
    r_s = float(jnp.sum((grads["w"] - z_s) ** 2))
    r_b = float(jnp.sum((grads["w"] - z_b) ** 2))
    assert r_b <= r_s + 1e-6
