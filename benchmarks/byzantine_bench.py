"""E4 (paper §4): byzantine norm-rescaling attack vs the paper's defense
(per-peer L2 normalization in the DCT domain + post-aggregation sign).

Setup: K honest peers + 1 byzantine peer that rescales its payload 1e4x.
We aggregate with each defense configuration and measure
  cos_clean   — cosine similarity of the aggregated update direction to
                the all-honest aggregate (1.0 = attack fully neutralized)
  loss_delta  — loss change after applying the update (negative = good)
Also: the no-attack control showing normalization costs nothing (paper:
"no impact on convergence in the fully cooperative setting").

``run_tokens`` closes the loop through the settled token economy
(``repro.econ``): byzantine peers attacking from round 0 accumulate
< 5% of an honest peer's cumulative ledger credits — the defense is not
just geometric, it is what keeps attackers unpaid.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.configs.base import TrainConfig
from repro.econ import profits
from repro.configs.registry import tiny_config
from repro.core import byzantine
from repro.data import pipeline
from repro.schemes import apply_update
from repro.schemes import demo as demo_opt
from repro.schemes import demo as compress
from repro.models import model as M


def _flat(tree):
    return jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                            for x in jax.tree.leaves(tree)])


def _cos(a, b):
    fa, fb = _flat(a), _flat(b)
    return float(fa @ fb / (jnp.linalg.norm(fa) * jnp.linalg.norm(fb)
                            + 1e-12))


def run(peers: int = 5, batch: int = 8, seq_len: int = 64, seed: int = 0):
    cfg = tiny_config()
    hp = TrainConfig(demo_chunk=16, demo_topk=8)
    corpus = pipeline.MarkovCorpus(cfg.vocab_size, seed=seed)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    metas = compress.tree_meta(params, hp.demo_chunk)
    grad = jax.jit(jax.grad(lambda p, b: M.loss_fn(p, b, cfg)[0]))
    loss_j = jax.jit(lambda p, b: M.loss_fn(p, b, cfg)[0])

    payloads = []
    for i in range(peers):
        b = pipeline.select_data(corpus, seed, f"p{i}", 0, batch, seq_len)
        g = grad(params, b)
        pl, _ = demo_opt.local_step(
            g, jax.tree.map(jnp.zeros_like, params), beta=hp.demo_beta,
            chunk=hp.demo_chunk, k=hp.demo_topk, metas=metas)
        payloads.append(pl)
    attacked = payloads[:-1] + [byzantine.norm_attack(payloads[-1])]

    eval_b = pipeline.unassigned_data(corpus, seed + 1, "eval", 0, 8, seq_len)
    l0 = float(loss_j(params, eval_b))
    lr = 2e-3

    def agg_loss(pls, normalize, apply_sign):
        delta = demo_opt.aggregate(pls, metas, normalize=normalize,
                                   apply_sign=apply_sign)
        p2 = apply_update(params, delta, lr)
        return delta, float(loss_j(p2, eval_b)) - l0

    clean_ref, _ = agg_loss(payloads, True, True)

    rows = []
    for label, pls, normalize, sign in [
        ("clean|norm+sign", payloads, True, True),
        ("clean|no-norm+sign", payloads, False, True),
        ("attack|norm+sign", attacked, True, True),
        ("attack|no-norm+sign", attacked, False, True),
        ("attack|norm only", attacked, True, False),
        ("attack|no defense", attacked, False, False),
    ]:
        delta, dl = agg_loss(pls, normalize, sign)
        rows.append({"config": label, "cos_to_clean": _cos(delta, clean_ref),
                     "loss_delta": dl})
    common.emit("byzantine_bench", rows,
                ["config", "cos_to_clean", "loss_delta"])

    by = {r["config"]: r for r in rows}
    # defense neutralizes the attack: direction ~= clean, loss still drops
    assert by["attack|norm+sign"]["cos_to_clean"] > 0.95
    assert by["attack|norm+sign"]["loss_delta"] < 0
    # normalization is free in the cooperative setting
    assert by["clean|no-norm+sign"]["cos_to_clean"] > 0.95
    # undefended attack destroys the update direction
    assert (by["attack|no defense"]["cos_to_clean"]
            < by["attack|norm+sign"]["cos_to_clean"] - 0.2)
    return rows


def run_tokens(rounds: int = 5, seed: int = 0):
    """Same attacks, settled in tokens via the sim's ledger
    (``repro.econ``).

    The *noise* attacker — pure Gaussian payload, zero contribution
    value — must earn well under half of an honest peer's cumulative
    ledger credits and strictly less profit than the honest mean. (Not
    < 5%: round 0 pays uniformly before any scores exist, and the
    Gauntlet is a noisy contribution market — the hard < 5% guarantee
    belongs to audit-*banned* peers, see ``audit_bench``.) The *norm*
    attacker gets the weaker-but-honest guarantee ``run`` proves
    geometrically: per-peer normalization makes its rescaled gradient
    equivalent to its honest one, so it is neutralized (the honest
    fleet keeps the credit majority) rather than defunded — a rescaled
    honest contribution is still a contribution."""
    from repro.sim import PeerSpec, Scenario, SimEngine

    honest = [f"worker-{i}" for i in range(5)]
    sc = Scenario(
        name="byzantine_economy", rounds=rounds, seed=seed,
        peers=tuple(PeerSpec(uid=u) for u in honest) + (
            PeerSpec(uid="byz-norm", behavior="byz_norm"),
            PeerSpec(uid="byz-noise", behavior="byz_noise"),
        ),
        description="norm/noise byzantines vs the settled token ledger")
    engine = SimEngine.from_scenario(sc, tiny_config(), batch=2,
                                     seq_len=32)
    engine.run()
    credits = {}
    for e in engine.chain.payouts():
        if e.kind == "credit" and e.uid in set(honest) | {"byz-norm",
                                                          "byz-noise"}:
            credits[e.uid] = credits.get(e.uid, 0.0) + e.amount
    honest_mean = sum(credits.get(u, 0.0) for u in honest) / len(honest)
    noise_credits = credits.get("byz-noise", 0.0)
    assert honest_mean > 0, credits
    assert noise_credits < 0.5 * honest_mean, (noise_credits,
                                               honest_mean, credits)
    honest_total = sum(credits.get(u, 0.0) for u in honest)
    assert honest_total > 0.5 * sum(credits.values()), credits
    # profit dominance: the noise attacker pays full operating cost for
    # a fraction of the pay
    profit = profits(engine.chain.balances(), engine.roi)
    honest_profit = sum(profit.get(u, 0.0) for u in honest) / len(honest)
    assert honest_profit > profit.get("byz-noise", 0.0), profit
    rows = [{"uid": u, "credits": credits.get(u, 0.0),
             "vs_honest": credits.get(u, 0.0) / honest_mean}
            for u in honest + ["byz-norm", "byz-noise"]]
    common.emit("byzantine_bench_tokens", rows,
                ["uid", "credits", "vs_honest"])
    print(f"byzantine token economics: noise attacker credits "
          f"{noise_credits:.2f} vs honest mean {honest_mean:.2f}; "
          f"honest fleet holds the credit majority and the profit edge "
          f"({honest_profit:+.2f} vs {profit.get('byz-noise', 0.0):+.2f})")
    return rows


if __name__ == "__main__":
    run()
    run_tokens()
