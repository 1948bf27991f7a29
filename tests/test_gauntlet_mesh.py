"""Multi-device Gauntlet: the shard_map'd round entry points must be a
pure performance knob.

A 1-device peer mesh must reproduce the no-mesh validator BIT-identically
(scores, audit flags, weights and aggregated params) for every gradient
scheme, the mesh path must keep the one-compile-per-entry-point
invariant across |S_t| churn, and a genuinely multi-device mesh (forced
host devices, subprocess — XLA device count locks at first jax init)
must still agree with the no-mesh pipeline: weights, flags and params
exactly, loss scores within MESH_SCORE_ATOL."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.configs.registry import tiny_config
from repro.launch.mesh import make_peer_mesh
from repro.sharding import MESH_SCORE_ATOL
from repro.training.peer import PeerConfig
from repro.training.round_loop import build_sim

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

PINNED = ("sync_scores", "fingerprint", "baselines", "primary")



def _hp(scheme):
    return TrainConfig(learning_rate=3e-3, warmup_steps=2,
                       total_steps=100, top_g=3, eval_set_size=6,
                       demo_chunk=16, demo_topk=8, poc_gamma=0.6,
                       eval_chunk=2, scheme=scheme)


def _run(scheme, mesh, rounds=2, sizes=None):
    cfg = tiny_config()
    pcs = [PeerConfig(uid=f"h{i}") for i in range(6)]
    v, peers, chain, store, corpus = build_sim(
        cfg, _hp(scheme), pcs, batch=2, seq_len=32, mesh=mesh)
    reports = []
    for rnd in range(rounds):
        for p in peers.values():
            p.produce(rnd)
        chain.advance(chain.blocks_per_round)
        active = [pc.uid for pc in pcs]
        if sizes is not None:
            active = active[:sizes[rnd]]
        reports.append(v.run_round(rnd, active))
    return v, reports


def _assert_identical(v0, r0, v1, r1, score_atol=0.0):
    """Weights, flags and params exactly; loss scores within
    ``score_atol`` (0 demands bit identity)."""
    for a, b in zip(r0, r1):
        for s0, s1 in ((a.loss_scores_assigned, b.loss_scores_assigned),
                       (a.loss_scores_rand, b.loss_scores_rand)):
            assert s0.keys() == s1.keys()
            np.testing.assert_allclose([s1[p] for p in s0],
                                       [s0[p] for p in s0],
                                       rtol=0, atol=score_atol)
        assert a.weights == b.weights
        assert a.audit_flagged == b.audit_flagged
    for x, y in zip(jax.tree.leaves(v0.params),
                    jax.tree.leaves(v1.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("scheme", ["demo", "randk"])
def test_one_device_mesh_bit_identical(scheme):
    v0, r0 = _run(scheme, mesh=None)
    v1, r1 = _run(scheme, mesh=make_peer_mesh())
    _assert_identical(v0, r0, v1, r1)


def test_mesh_path_one_compile_per_entry_across_churn():
    # churn |S_t| across rounds: the sticky pow2 buckets (now rounded to
    # a mesh-divisible multiple) must keep every shard_map'd entry point
    # at ONE trace
    v, _ = _run("demo", mesh=make_peer_mesh(), rounds=4,
                sizes=[6, 3, 5, 6])
    counts = v.trace_counts_all()
    for name in PINNED:
        assert counts.get(name, 0) == 1, (name, counts)


_MULTI = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import sys
    import jax
    import numpy as np
    sys.path.insert(0, {src!r})
    sys.path.insert(0, {here!r})
    from test_gauntlet_mesh import MESH_SCORE_ATOL, _run, _assert_identical
    from repro.launch.mesh import make_peer_mesh

    mesh = make_peer_mesh(4)
    v0, r0 = _run({scheme!r}, mesh=None)
    v1, r1 = _run({scheme!r}, mesh=mesh)
    _assert_identical(v0, r0, v1, r1, score_atol=MESH_SCORE_ATOL)
    counts = v1.trace_counts_all()
    print(json.dumps({{"traces": counts}}))
""")


@pytest.mark.parametrize("scheme", ["demo", "randk"])
def test_multi_device_mesh_matches_no_mesh(scheme):
    """4 forced host devices: sharded rounds agree with the no-mesh
    pipeline (subprocess — the parent keeps its single device)."""
    script = _MULTI.format(src=os.path.abspath(SRC),
                           here=os.path.dirname(os.path.abspath(__file__)),
                           scheme=scheme)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    for name in PINNED:
        assert payload["traces"].get(name, 0) == 1, payload
