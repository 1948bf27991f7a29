"""Static-shape padded round entry points (core.padding + core.gauntlet):
retrace regression across churn, chunked-vs-full and padded-vs-unpadded
parity (scores, flags, aggregated params), batched replay parity,
prefetch determinism, and exact-no-op padded aggregation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig
from repro.configs.registry import tiny_config
from repro.core import padding
from repro.core.gauntlet import Validator
from repro.schemes import demo as compress
from repro.schemes.demo import DemoScheme, Payload
from repro.training.peer import PeerConfig
from repro.training.round_loop import build_sim

HP = TrainConfig(learning_rate=3e-3, warmup_steps=2, total_steps=100,
                 top_g=3, eval_set_size=8, demo_chunk=16, demo_topk=8,
                 poc_gamma=0.6)


def _sim(n_peers: int, hp: TrainConfig = HP, extra=()):
    cfg = tiny_config()
    pcs = [PeerConfig(uid=f"h{i}") for i in range(n_peers)] + list(extra)
    return build_sim(cfg, hp, pcs, batch=4, seq_len=32)


def _publish(peers, chain, rnd: int):
    for peer in peers.values():
        peer.produce(rnd)
    chain.advance(chain.blocks_per_round)


def _leaves(tree):
    return jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, Payload))


# ------------------------------------------------------------- helpers

def test_pow2_bucket_growth_and_constraints():
    assert padding.pow2_bucket(1) == 1
    assert padding.pow2_bucket(3) == 4
    assert padding.pow2_bucket(5) == 8
    assert padding.pow2_bucket(8) == 8
    assert padding.pow2_bucket(2, minimum=4) == 4
    assert padding.pow2_bucket(5, multiple=3) == 9
    # the cap stops pow2 growth; above it the bucket tracks n exactly
    assert padding.pow2_bucket(9, cap=12) == 12
    assert padding.pow2_bucket(13, cap=12) == 13


def test_bucket_tracker_is_sticky():
    t = padding.BucketTracker(minimum=2)
    assert t.get("x", 5) == 8
    assert t.get("x", 3) == 8          # never shrinks
    assert t.get("x", 9) == 16         # grows on a new high-water mark
    assert t.get("y", 1) == 2          # independent axes
    assert t.peek("x") == 16 and t.peek("z") == 0


def test_pad_rows_zero_fills_to_bucket():
    rows = [np.full(3, i + 1.0, np.float32) for i in range(3)]
    mat = padding.pad_rows(rows, 3, bucket=8)
    assert mat.shape == (8, 3)
    np.testing.assert_array_equal(mat[:3], np.stack(rows))
    assert not mat[3:].any()
    # default bucket = next pow2; n > bucket is tolerated
    assert padding.pad_rows(rows, 3).shape == (4, 3)
    assert padding.pad_rows(rows, 3, bucket=2).shape == (3, 3)


def test_pad_axis0_zero_and_edge_modes():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)}
    z = padding.pad_axis0(tree, 4)
    assert z["a"].shape == (4, 3) and not np.any(np.asarray(z["a"][2:]))
    e = padding.pad_axis0(tree, 4, edge=True)
    np.testing.assert_array_equal(e["a"][2], e["a"][0])
    np.testing.assert_array_equal(e["a"][3], e["a"][0])


def test_pad_payloads_rows_are_exact_zero():
    p = Payload(vals=jnp.ones((2, 3, 4)), idx=jnp.ones((2, 3, 4),
                                                       jnp.int32))
    padded = compress.pad_payloads({"w": p}, 4)["w"]
    assert padded.vals.shape == (4, 3, 4)
    assert not np.any(np.asarray(padded.vals[2:]))
    assert not np.any(np.asarray(padded.idx[2:]))   # idx 0 = valid gather


# ------------------------------------------------- retrace regression

def test_one_trace_per_entry_point_across_churn():
    """Acceptance: rounds with |S_t| ∈ {3, 5, 8} and churning unique-
    batch counts add ZERO compiles after warmup — every padded entry
    point holds exactly one compiled shape."""
    validator, peers, chain, store, corpus = _sim(8)
    uids = list(peers)
    # warmup round at the high-water mark pins the sticky buckets
    _publish(peers, chain, 0)
    validator.run_round(0, uids)
    warm = validator.trace_counts_all()
    for name in ("sync_scores", "fingerprint", "baselines", "primary",
                 "sketch"):
        assert warm[name] == 1, (name, warm)
    for rnd, n in enumerate((3, 5, 8, 5), start=1):
        _publish(peers, chain, rnd)
        rep = validator.run_round(rnd, uids[:n])
        assert len(rep.evaluated) == n
    after = validator.trace_counts_all()
    for name in ("sync_scores", "fingerprint", "baselines", "primary",
                 "sketch", "aggregate"):
        assert after[name] == warm[name], (name, warm, after)


# ------------------------------------------------------------- parity

def _twin_validators(validator, chain, store, hp_a, hp_b):
    va = Validator("validator-a", validator.params, validator.scheme,
                   validator.eval_loss, hp_a, chain, store,
                   validator.data, rng=np.random.RandomState(hp_a.seed))
    vb = Validator("validator-b", validator.params, validator.scheme,
                   validator.eval_loss, hp_b, chain, store,
                   validator.data, rng=np.random.RandomState(hp_b.seed))
    return va, vb


def test_chunked_primary_matches_full_vmap():
    """Acceptance: lax.map-chunked primary eval is allclose to the
    full-vmap path on scores, weights AND the aggregated params."""
    validator, peers, chain, store, corpus = _sim(6)
    uids = list(peers)
    _publish(peers, chain, 0)
    va, vb = _twin_validators(
        validator, chain, store, HP,
        dataclasses.replace(HP, eval_chunk=2))
    ctx_a = va.run_stages(va.build_context(0, uids))
    ctx_b = vb.run_stages(vb.build_context(0, uids))
    assert ctx_a.eval_set == ctx_b.eval_set and len(ctx_a.eval_set) == 6
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
    # the chunked program's live-buffer footprint is strictly smaller
    mem_full = vb.primary_memory_analysis(eval_chunk=0)
    mem_chunk = vb.primary_memory_analysis()
    assert mem_chunk["temp_bytes"] < mem_full["temp_bytes"]


def test_padded_flags_match_on_copycat():
    """Audit flags are invariant to padding/chunking: a verbatim copycat
    is flagged identically by the full and chunked validators."""
    copy = PeerConfig(uid="copy-0", behavior="copycat", copy_victim="h0")
    validator, peers, chain, store, corpus = _sim(5, extra=[copy])
    uids = list(peers)
    _publish(peers, chain, 0)
    va, vb = _twin_validators(
        validator, chain, store, HP,
        dataclasses.replace(HP, eval_chunk=4))
    ctx_a = va.run_stages(va.build_context(0, uids))
    ctx_b = vb.run_stages(vb.build_context(0, uids))
    assert ctx_a.audit_flagged == ctx_b.audit_flagged
    # both detect the verbatim-copy cluster and flag exactly one member
    # (no replayer here, so arbitration is the earliest-upload heuristic;
    # WHO is kept only matters for parity, asserted above)
    assert ["copy-0", "h0"] in ctx_a.audit["clusters"]
    assert [r for r in ctx_a.audit_flagged.values()] == ["copy_cluster"]


def test_replay_batch_matches_scalar_replay():
    """The vmapped one-dispatch replay reproduces the per-target scalar
    local steps (satellite: cluster arbitration in one dispatch)."""
    validator, peers, chain, store, corpus = _sim(3)
    rp = validator._replayer
    batches = [validator.data["assigned"](p, 0) for p in list(peers)[:2]]
    singles = [rp.replay(validator.params, [b]) for b in batches]
    batched = rp.replay_batch(validator.params, batches)
    assert _leaves(batched)[0].vals.shape[0] >= 2   # padded bucket
    for i, single in enumerate(singles):
        dense_s = compress.decompress_tree(single, validator.scheme.metas)
        dense_b = compress.decompress_tree(
            jax.tree.map(lambda p: Payload(p.vals[i], p.idx[i]), batched,
                         is_leaf=lambda x: isinstance(x, Payload)),
            validator.scheme.metas)
        for ls, lb in zip(jax.tree.leaves(dense_s),
                          jax.tree.leaves(dense_b)):
            np.testing.assert_allclose(np.asarray(lb), np.asarray(ls),
                                       rtol=1e-5, atol=1e-6)


def test_prefetch_matches_sequential_fast_filter():
    """The thread-pool bucket-read prefetch changes wall-clock overlap
    only: fast-set, pass/fail map and cached payloads are identical."""
    validator, peers, chain, store, corpus = _sim(10)
    uids = list(peers)
    _publish(peers, chain, 0)
    va, vb = _twin_validators(
        validator, chain, store,
        dataclasses.replace(HP, fast_prefetch_workers=0),
        dataclasses.replace(HP, fast_prefetch_workers=2))
    ctx_a = va.build_context(0, uids, fast_set_size=10)
    ctx_b = vb.build_context(0, uids, fast_set_size=10)
    va.stage_fast_filter(ctx_a)
    vb.stage_fast_filter(ctx_b)
    assert ctx_b.fast_set == ctx_a.fast_set
    assert ctx_b.fast_pass == ctx_a.fast_pass
    assert ctx_b.sync_samples                     # prefetch actually ran
    assert set(ctx_a.payloads) == set(ctx_b.payloads)


def test_padded_aggregate_rows_are_exact_noops():
    """Zero-weight padded rows leave the aggregated params bit-identical
    to the unpadded call (the bit-identity contract validator and peer
    replicas rely on)."""
    params = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    scheme = DemoScheme(TrainConfig(demo_chunk=4, demo_topk=3), params)
    metas = scheme.metas
    payloads = [compress.compress_tree(
        jax.tree.map(lambda x: jnp.cos(x + i), params), metas, 3)
        for i in range(2)]
    stacked = compress.stack_payloads(payloads)
    base = scheme.aggregate_apply(
        params, stacked, jnp.arange(2, dtype=jnp.int32), jnp.float32(0.1))
    padded = compress.pad_payloads(stacked, 8)
    weights = jnp.asarray([0.5, 0.5] + [0.0] * 6, jnp.float32)
    rows = jnp.asarray([0, 1] + [0] * 6, jnp.int32)
    out = scheme.aggregate_apply(params, padded, rows, jnp.float32(0.1),
                                 weights)
    for lb, lo in zip(jax.tree.leaves(base), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(lb), np.asarray(lo))


def test_replay_cap_bounds_bucket_on_giant_cluster():
    """Satellite (ROADMAP PR-4 follow-up): an unusually large copy
    cluster must not grow the sticky replay bucket past the configured
    cap — worst-case replay cost is bounded, with no churn retrace —
    and capping must never flag an honest peer on missing evidence."""
    cap = 4
    hp = dataclasses.replace(HP, eval_set_size=12, audit_replay_cap=cap)
    ring = [PeerConfig(uid=f"copy-{i}", behavior="copycat_noise",
                       copy_victim="h0") for i in range(8)]
    validator, peers, chain, store, corpus = _sim(4, hp, extra=ring)
    uids = list(peers)
    for rnd in range(2):
        _publish(peers, chain, rnd)
        ctx = validator.run_stages(validator.build_context(rnd, uids))
        # zero false positives even though most of the cluster was
        # sampled away from replay this round
        assert not any(p.startswith("h") for p in ctx.audit_flagged), (
            rnd, ctx.audit_flagged)
        # the skipped targets are surfaced in the audit diagnostics
        if len(ctx.audit.get("clusters", [[]])[0]) > cap:
            assert ctx.audit.get("replay_capped", 0) > 0
    rp = validator._replayer
    assert rp is not None
    # the sticky replay bucket is pinned by the cap, not the cluster:
    # spot checks and delayed suspects never exceed cap by construction
    assert rp._pad.peek("replay") <= padding.pow2_bucket(cap, minimum=2), \
        rp._pad.peek("replay")
