"""The dropless expert layer against an explicit per-expert reference,
the shares of a layer held by different chips against the uncut layer,
and DeepSeek's router (no renormalisation, float32 logits, the
sequence-wise balance loss)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import reduced_config
from repro.models import layers, moe


def _cfg(**moe_kw):
    cfg = reduced_config("deepseek-moe-16b")
    return cfg.with_overrides(moe=dataclasses.replace(cfg.moe, **moe_kw))


def _reference_moe(p, x, cfg):
    """Dense reference: every held expert on every token, weighted by
    its gate where the token picked it and by zero elsewhere."""
    m = cfg.moe
    B, S, d = x.shape
    gates, eidx, _ = moe.route(p["router"], x, m)
    weight = jnp.sum(jax.nn.one_hot(eidx, m.num_experts) * gates[..., None],
                     axis=-2).reshape(B * S, m.num_experts)
    xt = x.reshape(-1, d)
    w = p["experts"]
    y = jnp.zeros_like(xt)
    for e in range(m.held):
        h = jax.nn.silu(xt @ w["gate"][e]) * (xt @ w["up"][e])
        y = y + (h @ w["down"][e]) * weight[:, m.expert_offset + e, None]
    y = y.reshape(B, S, d)
    if "shared" in p:
        y = y + layers.swiglu(p["shared"], x)
    return y


def test_dispatch_matches_dense_reference():
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    p = moe.init_moe(key, cfg)
    x = 0.1 * jax.random.normal(key, (2, 16, cfg.d_model))
    y, _, counts = moe.moe_ffn(p, x, cfg)
    y_ref = _reference_moe(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
    # every expert is held: all 2 x 16 x top_k assignments computed
    assert int(counts[0]) == 2 * 16 * cfg.moe.top_k


@pytest.mark.parametrize("held,offset", [(0, 0), (4, 4)])
def test_group_count_invariance(held, offset):
    """Dropless: a token's output depends on no other token, so the
    layer over the whole batch equals the layer over 4 token groups
    (``num_groups``, each sorted and gathered on its own) and over each
    sequence alone, with the same gradients and counts."""
    cfg = _cfg(experts_held=held, expert_offset=offset)
    key = jax.random.PRNGKey(1)
    p = moe.init_moe(key, cfg)
    x = 0.1 * jax.random.normal(key, (4, 16, cfg.d_model))

    def run(p, x, groups):
        y, aux, counts = moe.moe_ffn(p, x, cfg, num_groups=groups)
        return jnp.sum(y * jnp.cos(y)) + aux, (y, counts)

    (_, (y1, c1)), g1 = jax.value_and_grad(run, argnums=(0, 1),
                                           has_aux=True)(p, x, 1)
    (_, (y4, c4)), g4 = jax.value_and_grad(run, argnums=(0, 1),
                                           has_aux=True)(p, x, 4)
    ys = jnp.concatenate([moe.moe_ffn(p, x[i:i + 1], cfg)[0]
                          for i in range(4)])
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y4), atol=2e-5)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(ys), atol=2e-5)
    assert int(c1[0]) == int(c4[0]) and int(c1[1]) == int(c4[1])
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_dropless_under_a_biased_router():
    """A router biased so that one expert takes nearly every token: no
    assignment to a held expert is dropped, however many land on one
    (the capacity dispatch this replaces dropped all past 1.25x the
    mean), and the output still equals the per-expert reference."""
    cfg = _cfg()
    m = cfg.moe
    key = jax.random.PRNGKey(2)
    p = moe.init_moe(key, cfg)
    p["router"]["b"] = jnp.array([8.0] + [0.0] * (m.num_experts - 1))
    x = 0.1 * jax.random.normal(key, (2, 32, cfg.d_model))
    y, _, counts = moe.moe_ffn(p, x, cfg)
    _, eidx, _ = moe.route(p["router"], x, m)
    assert int(jnp.sum(eidx == 0)) == 64              # every token
    assert int(counts[0]) == 64 * m.top_k and int(counts[1]) == 64
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_reference_moe(p, x, cfg)),
                               atol=2e-5)


def test_shares_add_up_to_the_uncut_layer():
    """64 experts, top-6, split 8 ways as expert parallelism splits
    them: each share holds 8 experts and routes over all 64. The routed
    parts of the 8 shares, with the shared experts counted once, equal
    the layer that holds all 64."""
    base = reduced_config("deepseek-v2-lite")
    full = base.with_overrides(moe=dataclasses.replace(
        base.moe, num_experts=64, top_k=6, expert_d_ff=32))
    key = jax.random.PRNGKey(3)
    p = moe.init_moe(key, full)
    x = 0.5 * jax.random.normal(key, (2, 24, full.d_model))
    y_full, aux_full, _ = moe.moe_ffn(p, x, full)
    shared = layers.swiglu(p["shared"], x)
    total, held = -7 * shared, 0
    for i in range(8):
        cut = full.with_overrides(moe=dataclasses.replace(
            full.moe, experts_held=8, expert_offset=8 * i)).validate()
        share = dict(p, experts=jax.tree.map(lambda w: w[8 * i:8 * i + 8],
                                             p["experts"]))
        y, aux, counts = moe.moe_ffn(share, x, cut)
        total, held = total + y, held + int(counts[0])
        # the router and its balance loss are every share's alike
        np.testing.assert_allclose(float(aux), float(aux_full), rtol=1e-6)
    assert held == 2 * 24 * 6
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_full),
                               atol=2e-5)


def test_router_aux_loss_penalizes_imbalance():
    cfg = _cfg()
    m = cfg.moe
    T, E = 256, m.num_experts
    x_bal = jax.random.normal(jax.random.PRNGKey(3), (T, cfg.d_model))
    router = {"w": 0.5 * jax.random.normal(jax.random.PRNGKey(4),
                                           (cfg.d_model, E))}
    _, _, aux_bal = moe.route(router, x_bal, m)
    # collapse router: bias drives every token to experts 0 and 1
    router_bad = {"w": jnp.zeros((cfg.d_model, E)),
                  "b": jnp.array([10.0, 5.0] + [0.0] * (E - 2))}
    _, _, aux_bad = moe.route(router_bad, x_bal, m)
    assert float(aux_bad) > float(aux_bal) * 1.2, (
        float(aux_bad), float(aux_bal))


def test_aux_loss_is_sequence_wise():
    """DeepSeek's balance loss: per sequence, alpha * sum_i f_i P_i with
    f_i = E/(k S) * picks of expert i and P_i its mean probability, then
    the mean over sequences; two sequences each routed to one expert
    are unbalanced, however balanced the batch is."""
    cfg = _cfg()
    m = cfg.moe
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 10, cfg.d_model))
    router = {"w": jax.random.normal(jax.random.PRNGKey(8),
                                     (cfg.d_model, m.num_experts))}
    _, eidx, aux = moe.route(router, x, m)
    probs = np.asarray(jax.nn.softmax(
        np.asarray(x, np.float64) @ np.asarray(router["w"], np.float64)))
    E, k, S = m.num_experts, m.top_k, 10
    want = np.mean([sum(E / (k * S) * np.sum(np.asarray(eidx[b]) == i)
                        * probs[b, :, i].mean() for i in range(E))
                    for b in range(3)]) * m.router_aux_coef
    np.testing.assert_allclose(float(aux), want, rtol=1e-5)


def test_gates_normalized():
    """The gates are normalised as the softmax over all the experts
    normalises them, and no further: norm_topk_prob is false in every
    published DeepSeek config, so the top-k gates are the probabilities
    as they are, not renormalised over the k. The logits are float32
    whatever the activations' dtype."""
    cfg = _cfg()
    m = cfg.moe
    x = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.d_model))
    router = {"w": 0.05 * jax.random.normal(jax.random.PRNGKey(6),
                                            (cfg.d_model, m.num_experts))}
    gates, eidx, _ = moe.route(router, x.astype(jnp.bfloat16), m)
    assert gates.dtype == jnp.float32
    probs = jax.nn.softmax(
        jnp.asarray(x.astype(jnp.bfloat16), jnp.float32) @ router["w"],
        axis=-1)
    np.testing.assert_allclose(
        np.asarray(gates),
        np.asarray(jnp.take_along_axis(probs, eidx, -1)), atol=1e-5)
    assert float(jnp.max(gates.sum(-1))) < 0.99


@pytest.mark.parametrize("rows,slice_rows", [(96, 32), (96, 40)])
def test_sliced_product_matches_whole(monkeypatch, rows, slice_rows):
    """Rows run in slices (``moe.ROW_SLICE``) give the whole product and
    its gradients, whether the slices cut through groups or not, and
    leave the rows past the last group at zero."""
    key = jax.random.PRNGKey(3)
    kx, kw, kg = jax.random.split(key, 3)
    x = jax.random.normal(kx, (rows, 16))
    w = jax.random.normal(kw, (3, 16, 8))
    g = jax.random.normal(kg, (rows, 8))
    sizes = jnp.array([30, 11, 37], jnp.int32)          # 78 rows filled

    def loss(f, x, w):
        return jnp.sum(f(x, w, sizes) * g)

    whole = jax.value_and_grad(functools.partial(loss, moe.grouped_matmul),
                               argnums=(0, 1))(x, w)
    monkeypatch.setattr(moe, "ROW_SLICE", slice_rows)
    y = moe.sliced_matmul(x, w, sizes)
    sliced = jax.value_and_grad(functools.partial(loss, moe.sliced_matmul),
                                argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        moe.grouped_matmul(x, w, sizes)), atol=1e-5)
    assert float(jnp.max(jnp.abs(y[78:]))) == 0.0
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(sliced)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-4)


@pytest.mark.parametrize("held,offset,slice_rows", [
    (2, 0, None), (1, 3, None), (2, 2, 40)])
def test_rows_past_last_group_are_never_read(monkeypatch, held, offset,
                                             slice_rows):
    """A grouped product that leaves the rows past its last group as
    NaN, and that reads them when it sums the weight gradient (as a
    kernel over whole row tiles does, times a zero), changes neither the
    layer's output nor any gradient."""
    cfg = _cfg(experts_held=held, expert_offset=offset)
    key = jax.random.PRNGKey(4)
    p = moe.init_moe(key, cfg)
    x = 0.1 * jax.random.normal(key, (2, 16, cfg.d_model))

    def run(p, x):
        y, aux, _ = moe.moe_ffn(p, x, cfg)
        return jnp.sum(y * jnp.cos(y)) + aux, y

    clean = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(p, x)

    def tail(a, sizes):
        return (jnp.arange(a.shape[0]) >= jnp.sum(sizes))[:, None]

    def nan_tail(a, sizes):
        return jnp.where(tail(a, sizes), jnp.nan, a)

    def tile_read(x, g, sizes):
        return 0.0 * (jnp.sum(jnp.where(tail(x, sizes), x, 0))
                      * jnp.sum(jnp.where(tail(g, sizes), g, 0)))

    prod, dx, dw = moe._product, moe._product_dx, moe._product_dw
    monkeypatch.setattr(moe, "_product",
                        lambda x, w, s: nan_tail(prod(x, w, s), s))
    monkeypatch.setattr(moe, "_product_dx",
                        lambda x, w, s, g: nan_tail(dx(x, w, s, g), s))
    monkeypatch.setattr(moe, "_product_dw", lambda x, w, s, g: (
        dw(x, w, s, g) + tile_read(x, g, s).astype(w.dtype)))
    if slice_rows:
        monkeypatch.setattr(moe, "ROW_SLICE", slice_rows)
    poisoned = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(p, x)
    for a, b in zip(jax.tree.leaves(clean), jax.tree.leaves(poisoned)):
        assert bool(jnp.all(jnp.isfinite(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
