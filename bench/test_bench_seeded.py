"""Inputs made from ``--seed``, their layout for the program, and the
reference codec's transform."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import seeded
import tinycell


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(tinycell.BENCH, "configs", "qwen2-1.5b.json")) as f:
        c = json.load(f)
    c.update(num_hidden_layers=3, hidden_size=32, num_attention_heads=4,
             num_key_value_heads=2, head_dim=8, intermediate_size=64,
             vocab_size=100, logit_rows=128)
    return c


def test_seed_words_keep_large_seeds_whole():
    assert seeded.seed_words(2 ** 33 + 5) == (5, 2)
    with pytest.raises(ValueError):
        seeded.seed_words(-1)


def test_weights_follow_the_seed(config):
    a = seeded.canonical_weights(config, 2 ** 40 + 1)
    b = seeded.canonical_weights(config, 2 ** 40 + 1)
    c = seeded.canonical_weights(config, 1)
    wq = ("layers", "attn", "wq", "w")
    get = lambda t: t[wq[0]][wq[1]][wq[2]][wq[3]]  # noqa: E731
    assert jnp.array_equal(get(a), get(b))
    assert not jnp.array_equal(get(a), get(c))
    assert get(a).shape == (3, 32, 32) and get(a).dtype == jnp.float32
    assert float(jnp.std(get(a))) == pytest.approx(1 / np.sqrt(32), rel=0.2)
    assert jnp.all(a["layers"]["norm1"]["g"] == 1)
    assert jnp.all(a["layers"]["attn"]["wk"]["b"] == 0)
    assert "lm_head" not in a                   # tied head


def test_batches_rows_all_differ():
    pool = seeded.token_batches(2 ** 35, 3, 4, 16, 50)
    rows = np.concatenate([np.asarray(b["tokens"]) for b in pool])
    assert len({r.tobytes() for r in rows}) == 12
    assert rows.min() >= 0 and rows.max() < 50
    assert np.array_equal(np.asarray(pool[0]["tokens"])[:, 1:],
                          np.asarray(pool[0]["labels"])[:, :-1])


def test_program_layouts(config):
    canon = seeded.canonical_weights(config, 7)
    sds = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    rest = {k: v for k, v in canon.items() if k != "layers"}
    stacked = dict(rest, groups=[canon["layers"]])
    unrolled = dict(rest, layers=[jax.tree.map(lambda x: x[i],
                                               canon["layers"])
                                  for i in range(3)])
    got = seeded.to_program(canon, sds(stacked))
    assert got["groups"][0]["attn"]["wq"]["w"] is \
        canon["layers"]["attn"]["wq"]["w"]
    got = seeded.to_program(canon, sds(unrolled))
    assert jnp.array_equal(got["layers"][2]["mlp"]["up"]["w"],
                           canon["layers"]["mlp"]["up"]["w"][2])
    assert "layers.attn.wq.b" in seeded.leaf_names(stacked)
    assert "layers.2.attn.wq.b" in seeded.leaf_names(unrolled)
    bad = dict(rest, groups=[dict(canon["layers"], norm1={"g": jnp.ones(3)})])
    with pytest.raises(ValueError):
        seeded.to_program(canon, sds(bad))


@pytest.mark.parametrize("shape", [(100,), (3, 70, 20), (64, 128)])
def test_reference_codec_round_trip(shape):
    """Keeping every coefficient sends the whole tensor: the error
    feedback is left at zero and the update is its sign."""
    ref = harness.load_module(os.path.join(tinycell.BENCH, "reference",
                                           "dense.py"), "ref_codec")
    g = jax.random.normal(jax.random.PRNGKey(0), shape)
    with jax.default_matmul_precision("highest"):
        e, d = ref.demo_leaf(jnp.zeros(shape), g, beta=0.9, chunk=8,
                             topk=64)
    assert float(jnp.max(jnp.abs(e))) < 1e-5
    assert jnp.array_equal(d, jnp.sign(g))
    e, _ = ref.demo_leaf(jnp.zeros(shape), g, beta=0.9, chunk=8, topk=4)
    # an orthonormal transform: what is kept and what is left add up
    assert float(jnp.sum(e * e)) < float(jnp.sum(g * g))
