"""Scheme-agnostic Gauntlet: the shared GradScheme parity suite.

Every registered scheme must pass the same contract: round scores /
flags / aggregated params consistent across ``eval_chunk`` settings, one
compile per jitted entry point across |S_t| churn, replica bit-identity,
and the copycat_ring audit economics (copies earn <5% of honest
incentive at zero false positives). ``demo`` (the paper's DCT-top-k
DeMo codec) and ``randk`` (seeded random-k + sign-SGD) both run it —
the acceptance behind the paper's "applies to any synchronous scheme"
portability claim.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.configs.registry import tiny_config
from repro.core.gauntlet import Validator
from repro.schemes import SCHEMES, get_scheme, make_scheme
from repro.schemes.randk import RandKScheme, batch_seed
from repro.sim import SimEngine, get_scenario
from repro.training.peer import PeerConfig
from repro.training.round_loop import build_sim

CFG = tiny_config()
SCHEME_NAMES = ["demo", "randk"]


def _hp(scheme: str, **kw) -> TrainConfig:
    base = dict(learning_rate=3e-3, warmup_steps=2, total_steps=100,
                top_g=3, eval_set_size=8, demo_chunk=16, demo_topk=8,
                randk_frac=0.05, poc_gamma=0.6, scheme=scheme)
    base.update(kw)
    return TrainConfig(**base)


def _publish(peers, chain, rnd: int):
    for peer in peers.values():
        peer.produce(rnd)
    chain.advance(chain.blocks_per_round)


# ------------------------------------------------------------- registry


def test_registry_has_both_schemes():
    assert {"demo", "randk"} <= set(SCHEMES)
    with pytest.raises(KeyError):
        get_scheme("no-such-scheme")


def test_make_scheme_dispatches_on_hp():
    params = {"w": jnp.zeros((8, 8)), "b": jnp.zeros((5,))}
    assert make_scheme(_hp("demo"), params).name == "demo"
    assert make_scheme(_hp("randk"), params).name == "randk"


def test_schemes_reject_each_others_payloads():
    """Format validation is part of the scheme contract: a payload in
    the wrong wire format must fail §3.2 check (c), whatever scheme the
    validator runs."""
    params = {"w": jnp.ones((8, 8)), "b": jnp.ones((5,))}
    demo = make_scheme(_hp("demo"), params)
    randk = make_scheme(_hp("randk"), params)
    p_demo = demo.compress(params)
    p_randk = randk.compress(params)
    assert demo.format_ok(p_demo) and randk.format_ok(p_randk)
    assert not demo.format_ok(p_randk)
    assert not randk.format_ok(p_demo)
    assert not demo.format_ok({"w": 1})
    assert not randk.format_ok(None)


# -------------------------------------------------- scheme-generic ops


@pytest.mark.parametrize("scheme_name", SCHEME_NAMES)
def test_stack_pad_take_roundtrip(scheme_name):
    params = {"w": jnp.ones((8, 8)), "b": jnp.ones((5,))}
    scheme = make_scheme(_hp(scheme_name), params)
    payloads = [scheme.compress(jax.tree.map(lambda x: x * (i + 1),
                                             params), seed=i)
                for i in range(3)]
    stacked = scheme.stack_payloads(payloads)
    assert scheme.payload_rows(stacked) == 3
    padded = scheme.pad_payloads(stacked, 8)
    assert scheme.payload_rows(padded) == 8
    # padded rows are exact zeros (maskable no-ops downstream)
    for leaf in jax.tree.leaves(padded):
        assert not np.any(np.asarray(leaf[3:]))
    # take recovers the original rows
    taken = scheme.take_payloads(padded, jnp.asarray([2, 0]))
    for got, want in zip(jax.tree.leaves(taken),
                         jax.tree.leaves(stacked)):
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[2]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[0]))


@pytest.mark.parametrize("scheme_name", SCHEME_NAMES)
def test_padded_aggregate_rows_are_exact_noops(scheme_name):
    """Zero-weight padded rows leave the aggregated params bit-identical
    to the unpadded call — the bit-identity contract validator and peer
    replicas rely on, scheme-generic."""
    params = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    scheme = make_scheme(_hp(scheme_name, demo_chunk=4, demo_topk=3),
                         params)
    payloads = [scheme.compress(
        jax.tree.map(lambda x: jnp.cos(x + i), params), seed=i)
        for i in range(2)]
    stacked = scheme.stack_payloads(payloads)
    base = scheme.aggregate_apply(
        params, stacked, jnp.arange(2, dtype=jnp.int32), jnp.float32(0.1))
    padded = scheme.pad_payloads(stacked, 8)
    weights = jnp.asarray([0.5, 0.5] + [0.0] * 6, jnp.float32)
    rows = jnp.asarray([0, 1] + [0] * 6, jnp.int32)
    out = scheme.aggregate_apply(params, padded, rows, jnp.float32(0.1),
                                 weights)
    for lb, lo in zip(jax.tree.leaves(base), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(lb), np.asarray(lo))


@pytest.mark.parametrize("scheme_name", SCHEME_NAMES)
def test_norm_attack_is_neutralized_by_aggregation(scheme_name):
    """Per-peer normalization + sign: a 1e6x-rescaled payload moves the
    aggregate exactly as far as its honest original would."""
    from repro.core import byzantine
    params = {"w": jnp.linspace(-1, 1, 64).reshape(8, 8)}
    scheme = make_scheme(_hp(scheme_name, demo_chunk=4, demo_topk=3),
                         params)
    honest = [scheme.compress(
        jax.tree.map(lambda x: jnp.sin(x + i), params), seed=i)
        for i in range(3)]
    rows = jnp.arange(3, dtype=jnp.int32)
    base = scheme.aggregate_apply(params, scheme.stack_payloads(honest),
                                  rows, jnp.float32(0.1))
    attacked = honest[:2] + [byzantine.norm_attack(honest[2], 1e6)]
    out = scheme.aggregate_apply(params, scheme.stack_payloads(attacked),
                                 rows, jnp.float32(0.1))
    for lb, lo in zip(jax.tree.leaves(base), jax.tree.leaves(out)):
        np.testing.assert_allclose(np.asarray(lo), np.asarray(lb),
                                   rtol=1e-5, atol=1e-6)


# --------------------------------------------------- randk specifics


def test_randk_index_selection_is_batch_seeded():
    """The kept coordinates derive from the consumed batch's content:
    same batch → same layout (what makes replay audits line up),
    different batch → a different pseudo-random subset."""
    params = {"w": jnp.zeros((16, 16)), "b": jnp.zeros((40,))}
    scheme = RandKScheme(_hp("randk", randk_frac=0.1), params)
    grads = jax.tree.map(lambda x: jnp.ones_like(x), params)
    b1 = {"tokens": jnp.arange(32, dtype=jnp.int32).reshape(2, 16)}
    b2 = {"tokens": jnp.arange(32, dtype=jnp.int32).reshape(2, 16) + 1}
    p1, _ = scheme.local_step(grads, scheme.init_state(params), batch=b1)
    p1b, _ = scheme.local_step(grads, scheme.init_state(params), batch=b1)
    p2, _ = scheme.local_step(grads, scheme.init_state(params), batch=b2)
    np.testing.assert_array_equal(np.asarray(p1["w"].idx),
                                  np.asarray(p1b["w"].idx))
    assert not np.array_equal(np.asarray(p1["w"].idx),
                              np.asarray(p2["w"].idx))
    # distinct positions within a leaf, in range
    idx = np.asarray(p1["w"].idx)
    assert len(set(idx.tolist())) == idx.size
    assert idx.min() >= 0 and idx.max() < 256
    # seeds themselves are content-derived and deterministic
    assert int(batch_seed(b1)) == int(batch_seed(b1))
    assert int(batch_seed(b1)) != int(batch_seed(b2))


def test_randk_error_feedback_removes_shipped_coordinates():
    params = {"w": jnp.zeros((10, 10))}
    scheme = RandKScheme(_hp("randk", randk_frac=0.08), params)
    grads = {"w": jnp.linspace(1.0, 2.0, 100).reshape(10, 10)}
    batch = {"tokens": jnp.ones((2, 8), jnp.int32)}
    payload, ef = scheme.local_step(grads, scheme.init_state(params),
                                    batch=batch)
    ef = np.asarray(ef["w"]).reshape(-1)
    idx = np.asarray(payload["w"].idx)
    # shipped coordinates left the buffer; the rest accumulated
    np.testing.assert_allclose(ef[idx], 0.0, atol=1e-7)
    mask = np.ones(100, bool)
    mask[idx] = False
    np.testing.assert_allclose(
        ef[mask], np.asarray(grads["w"]).reshape(-1)[mask], rtol=1e-6)


# ------------------------------------- the shared round-parity suite


@pytest.mark.parametrize("scheme_name", SCHEME_NAMES)
def test_round_parity_chunking_and_churn_traces(scheme_name):
    """The PR-4 invariants, scheme-generic: chunked primary eval is
    allclose to full-vmap on scores/flags/weights/params, and churn in
    |S_t| adds ZERO compiles per jitted entry point after warmup."""
    hp = _hp(scheme_name)
    pcs = [PeerConfig(uid=f"h{i}") for i in range(8)]
    validator, peers, chain, store, corpus = build_sim(
        CFG, hp, pcs, batch=2, seq_len=32)
    uids = list(peers)
    _publish(peers, chain, 0)
    va = Validator("validator-a", validator.params, validator.scheme,
                   validator.eval_loss, hp, chain, store, validator.data,
                   rng=np.random.RandomState(hp.seed))
    vb = Validator("validator-b", validator.params, validator.scheme,
                   validator.eval_loss,
                   dataclasses.replace(hp, eval_chunk=2), chain, store,
                   validator.data, rng=np.random.RandomState(hp.seed))
    ctx_a = va.run_stages(va.build_context(0, uids))
    ctx_b = vb.run_stages(vb.build_context(0, uids))
    assert ctx_a.eval_set == ctx_b.eval_set and len(ctx_a.eval_set) == 8
    for p in ctx_a.eval_set:
        np.testing.assert_allclose(ctx_b.loss_scores_assigned[p],
                                   ctx_a.loss_scores_assigned[p],
                                   rtol=1e-5, atol=1e-6, err_msg=p)
        np.testing.assert_allclose(ctx_b.loss_scores_rand[p],
                                   ctx_a.loss_scores_rand[p],
                                   rtol=1e-5, atol=1e-6, err_msg=p)
    assert ctx_a.audit_flagged == ctx_b.audit_flagged == {}
    assert ctx_a.weights.keys() == ctx_b.weights.keys()
    for p in ctx_a.weights:
        np.testing.assert_allclose(ctx_b.weights[p], ctx_a.weights[p],
                                   rtol=1e-6, err_msg=p)
    for la, lb in zip(jax.tree.leaves(va.params),
                      jax.tree.leaves(vb.params)):
        np.testing.assert_allclose(np.asarray(lb), np.asarray(la),
                                   rtol=1e-6, atol=1e-6)
    # one compile per entry point across churn (|S_t| ∈ {3, 5, 8})
    warm = va.trace_counts_all()
    for name in ("sync_scores", "fingerprint", "baselines", "primary"):
        assert warm[name] == 1, (scheme_name, name, warm)
    for rnd, n in enumerate((3, 5, 8), start=1):
        _publish(peers, chain, rnd)
        rep = va.run_round(rnd, uids[:n])
        assert len(rep.evaluated) == n
    after = va.trace_counts_all()
    for name in ("sync_scores", "fingerprint", "baselines", "primary",
                 "aggregate"):
        assert after[name] == warm[name], (scheme_name, name, warm, after)


@pytest.mark.parametrize("scheme_name", SCHEME_NAMES)
def test_copycat_ring_economics_and_bit_identity(scheme_name):
    """Acceptance: both schemes run copycat_ring end-to-end with every
    copy earning <5% of honest incentive, zero false positives, and all
    replicas (validator + peers) bit-identical."""
    sc = dataclasses.replace(
        get_scenario("copycat_ring", rounds=3, seed=0),
        scheme=scheme_name)
    eng = SimEngine.from_scenario(sc, CFG, batch=2, seq_len=32)
    eng.run()
    v = list(eng.validators.values())[0]
    assert v.scheme.name == scheme_name
    honest = [f"worker-{i}" for i in range(5)]
    ring = ["ring-verbatim", "ring-delayed", "ring-noise"]
    flagged_ever = set()
    for rep in eng.reports[v.uid]:
        flagged_ever |= set(rep.audit_flagged)
        assert not (set(rep.audit_flagged) & set(honest)), (
            scheme_name, rep.round_idx, rep.audit_flagged)
    assert {"ring-verbatim", "ring-noise"} <= flagged_ever, (
        scheme_name, flagged_ever)
    consensus = eng.chain.consensus_weights()
    honest_mean = np.mean([consensus.get(p, 0.0) for p in honest])
    assert honest_mean > 0
    for cc in ring:
        assert consensus.get(cc, 0.0) < 0.05 * honest_mean, (
            scheme_name, cc, consensus)
    # replica bit-identity across the whole fleet
    ref = jax.tree.leaves(v.params)
    for uid, peer in eng.peers.items():
        for x, y in zip(ref, jax.tree.leaves(peer.params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"{scheme_name}:{uid}")
