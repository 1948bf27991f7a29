"""DeepSeek-V2-Lite at a small size on the CPU against the plain float32
reference (``bench/reference/mla_moe.py``) on seeded weights: YaRN,
the loss and every gradient, one DeMo step of ``make_step`` (the
benchmark cell's program), and the validator's path."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import reduced_config
from repro.models import layers, mla
from repro.models import model as M

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")
sys.path.insert(0, BENCH)
import compare  # noqa: E402
import harness  # noqa: E402

entry = harness.load_module(os.path.join(BENCH, "entries", "peer_step_moe.py"),
                            "bench_entry_peer_step_moe")
ref = harness.load_module(os.path.join(BENCH, "reference", "mla_moe.py"),
                          "bench_ref_mla_moe")
SEED = 2 ** 33 + 11


def _config(held=0, offset=0):
    """reduced_config("deepseek-v2-lite") with a second expert layer, so
    that the expert layers are scanned as a stacked group, holding
    ``held`` experts from ``offset`` (all by default)."""
    cfg = reduced_config("deepseek-v2-lite").with_overrides(num_layers=3)
    return cfg.with_overrides(moe=dataclasses.replace(
        cfg.moe, experts_held=held, expert_offset=offset)).validate()


def _config_file(cfg) -> dict:
    """The benchmark's configuration file at ``cfg``'s sizes."""
    with open(os.path.join(BENCH, "configs", "deepseek-v2-lite.json")) as f:
        c = json.load(f)
    m, a = cfg.moe, cfg.mla
    c.update(num_hidden_layers=cfg.num_layers, hidden_size=cfg.d_model,
             num_attention_heads=cfg.num_heads,
             num_key_value_heads=cfg.num_kv_heads,
             intermediate_size=cfg.d_ff, vocab_size=cfg.vocab_size,
             logit_rows=cfg.padded_vocab, kv_lora_rank=a.kv_lora_rank,
             qk_nope_head_dim=a.qk_nope_head_dim,
             qk_rope_head_dim=a.qk_rope_head_dim, v_head_dim=a.v_head_dim,
             moe_intermediate_size=m.expert_d_ff,
             router_outputs=m.num_experts, n_routed_experts=m.held,
             expert_offset=m.expert_offset, num_experts_per_tok=m.top_k,
             n_shared_experts=m.num_shared_experts,
             compute_dtype=cfg.dtype, param_dtype=cfg.param_dtype)
    return c


def test_yarn_frequencies_and_scale():
    """DeepSeek-V2-Lite's YaRN: factor 40 over 4,096 positions, betas
    32 and 1, mscale = mscale_all_dim = 0.707, 64 rope dims."""
    cfg = reduced_config("deepseek-v2-lite").with_overrides(
        mla=dataclasses.replace(reduced_config("deepseek-v2-lite").mla,
                                qk_rope_head_dim=64, qk_nope_head_dim=128))
    y = cfg.rope_scaling
    assert (y.factor, y.original_max_position, y.beta_fast, y.beta_slow,
            y.mscale, y.mscale_all_dim) == (40, 4096, 32, 1, 0.707, 0.707)
    i = np.arange(32, dtype=np.float64)
    extra = 1e4 ** (-2 * i / 64)
    # correction dims: 64 ln(4096 / (2 pi beta)) / (2 ln 1e4)
    low, high = 10, 23       # floor(10.47), ceil(22.49)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    got = np.asarray(layers.rope_freqs(64, 1e4, y))
    np.testing.assert_allclose(got, want, rtol=1e-6)   # float32 rounding
    np.testing.assert_allclose(ref.yarn(_config_file(cfg))[0], want,
                               rtol=1e-6)
    mscale = 0.1 * 0.707 * np.log(40) + 1
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2)
    assert ref.yarn(_config_file(cfg))[1:] == pytest.approx(
        (1.0, 192 ** -0.5 * mscale ** 2))
    # the plain rope is what it was, where no config sets YaRN
    np.testing.assert_array_equal(
        np.asarray(layers.rope_freqs(64, 1e4)),
        np.asarray(1.0 / (1e4 ** (jnp.arange(0, 64, 2, dtype=jnp.float32)
                                  / 64))))


@pytest.mark.parametrize("held,offset", [(0, 0), (2, 2)],
                         ids=["all-experts", "held-2-of-4"])
def test_loss_and_gradients_match_reference(held, offset):
    """The loss ``make_step`` differentiates (scanned layers, remat) and
    every gradient, against the reference's on the same seeded weights
    and tokens. Both are float32 (the reference at HIGHEST precision);
    the tolerance is float32 round-off through 3 layers and the loss."""
    cfg = _config(held, offset)
    c = _config_file(cfg)
    got = entry.program_config(c)      # all that shapes the model
    assert got.moe.held == cfg.moe.held
    assert got.with_overrides(
        name=cfg.name, head_dim=cfg.head_dim, max_seq_len=cfg.max_seq_len,
        moe=cfg.moe) == cfg
    canon = entry.canonical_weights(c, SEED)
    p_sds = jax.eval_shape(lambda: M.init_params_stacked(
        cfg, jax.random.PRNGKey(0)))
    params = entry.to_program(canon, p_sds)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 33), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    loss, grads = jax.jit(jax.value_and_grad(lambda p: M.loss_fn(
        p, batch, cfg, remat=True, scan_layers=True)[0]))(params)
    with jax.default_matmul_precision("highest"):
        rloss, rgrads = jax.jit(jax.value_and_grad(lambda p: ref.loss(
            c, ref.dense.matmul("float32"), p, batch["tokens"],
            batch["labels"])))(canon)
    assert float(loss) == pytest.approx(float(rloss), rel=2e-6)
    names = entry.leaf_names(p_sds)
    rnamed = dict(zip(*zip(*[(".".join(str(k.key) for k in path), g)
                             for path, g in jax.tree_util.tree_flatten_with_path(
                                 rgrads)[0]])))
    for name, g in zip(names, jax.tree.leaves(grads)):
        r = np.asarray(rnamed[name])
        scale = float(np.max(np.abs(r))) + 1e-12
        np.testing.assert_allclose(np.asarray(g) / scale, r / scale,
                                   atol=2e-4, err_msg=name)


def test_demo_step_matches_reference():
    """The benchmark cell's program (``make_step``, DeMo, two
    micro-batches) at the small size: the first step's loss and error
    feedback and the parameters' change over three steps, against the
    reference, float32 on both sides. A few of the k largest of 4,096
    DCT coefficients per chunk may trade places on round-off, so the
    comparison is the benchmark's own (norms and cosines per tensor),
    with limits a thousand times under the chip cell's."""
    cfg = _config(2, 2)
    c = _config_file(cfg)
    t = {"entry": "peer_step_moe", "batch": 4, "seq": 32, "microbatch": 2,
         "pool": 3, "check_steps": 3, "learning_rate": 4e-4,
         "warmup_steps": 0, "total_steps": 100, "lr_min_frac": 0.1,
         "weight_decay": 0.1, "demo_beta": 0.999, "demo_chunk": 16,
         "demo_topk": 8}
    cell = harness.Cell(name="t", config_name="t", traffic_name="t",
                        chips=1, config=c, traffic=t, limits={},
                        end_to_end=[], per_layer=[],
                        root=os.path.dirname(BENCH))
    prog = entry.Program(cell)
    pool = prog.batches(SEED)
    with jax.set_mesh(prog.mesh):
        got = prog.first_steps(SEED, pool)[2]
    gaps = compare.gaps(got, prog.reference(cell, SEED, "float32"))
    assert gaps["loss_gap"][0] < 1e-5, gaps
    for name in ("ef1_gap", "ef1_cos_gap", "change_gap", "change_cos_gap"):
        assert gaps[name][0] < 1e-4, (name, gaps[name])


def test_validator_round_runs_the_dropless_layer():
    """The validator's path on the small DeepSeek-V2-Lite: one round of
    the Gauntlet with the replay audit (``shared_replay_step`` vmaps the
    peers' local step over the audited peers), honest peers and one lazy
    peer, which the commitment check flags as it does for ``tiny``."""
    from repro.configs.base import TrainConfig
    from repro.training.peer import PeerConfig
    from repro.training.round_loop import build_sim, run_rounds

    cfg = reduced_config("deepseek-v2-lite")
    hp = TrainConfig(warmup_steps=2, total_steps=100, top_g=2,
                     eval_set_size=3, eval_chunk=1, demo_chunk=16,
                     demo_topk=8)
    pcs = [PeerConfig(uid="honest-0"), PeerConfig(uid="honest-1"),
           PeerConfig(uid="lazy-0", behavior="lazy")]
    v, peers, chain, _, _ = build_sim(cfg, hp, pcs, batch=2, seq_len=16,
                                      eval_batch=2)
    res = run_rounds(v, peers, chain, 2)
    flagged = {u: r for rep in res.reports
               for u, r in rep.audit_flagged.items()}
    assert flagged == {"lazy-0": "commit_mismatch"}
    assert all(np.isfinite(s) for rep in res.reports
               for s in rep.loss_scores_assigned.values())
