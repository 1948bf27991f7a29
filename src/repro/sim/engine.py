"""Discrete-event testnet engine: the paper's permissionless network as a
seeded, block-accurate simulation.

The event queue is keyed to :class:`repro.comms.chain.Chain` blocks — the
same clock the put-window enforcement reads — so everything that makes a
live network hard is an *event*, not a hard-coded peer behaviour:

* **churn** — peers join (bootstrapping their replica from the chain's
  checkpoint pointer) and leave (their bucket vanishes, possibly with a
  put still in flight);
* **delayed arrivals** — :class:`repro.sim.network.SimBucketStore` turns
  bucket puts into arrival events whose delay is bandwidth-proportional
  in the payload bytes;
* **adversary schedules** — behaviour flips at scheduled rounds compose
  the ``repro.core.byzantine`` transforms over time (honest-then-turncoat);
* **validator failover** — staked validators go dark and recover,
  re-pointing the chain checkpoint and resyncing from it.

Multiple validators run concurrent round pipelines against the same chain
and buckets: each posts its weights (``Chain.post_weights``), incentive
resolves through the stake-weighted median (``Chain.consensus_weights``),
every replica aggregates with the *consensus* top-G so the fleet stays
bit-identical, and redundant validators skip the baseline-loss work via
the shared :class:`repro.core.gauntlet.BaselineCache` keyed through the
checkpoint pointer.

``repro.training.round_loop.run_rounds`` is a thin compatibility wrapper
over this engine (single validator, perfect network, no churn).
"""
from __future__ import annotations

import dataclasses
import heapq
import json
import math
import zlib
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.audit import assignment as audit_assignment
from repro.comms.chain import Chain
from repro.core import scores as S
from repro.core.gauntlet import BaselineCache, RoundReport, Validator
from repro.econ import (EconConfig, PayoutLedger, behavior_cost,
                        round_emission, settle_round)
from repro.obs.explain import explain_round
from repro.sim.network import NetworkModel, SimBucketStore
from repro.sim.scenario import PeerSpec, Scenario
from repro.sim.telemetry import HONEST_BEHAVIORS, Telemetry
from repro.training.peer import PeerConfig, PeerNode


class SimEngine:
    """Schedules and drives one scenario run.

    Can be constructed around pre-built components (the ``run_rounds``
    compatibility path) or from a declarative :class:`Scenario` via
    :meth:`from_scenario`.
    """

    def __init__(self, chain: Chain, store, validators: List[Validator],
                 peers: Dict[str, PeerNode], *,
                 telemetry: Optional[Telemetry] = None,
                 grad_fn: Optional[Callable] = None,
                 fast_set_size: Optional[int] = None,
                 eval_every: int = 5,
                 eval_batch_fn: Optional[Callable] = None,
                 obs=None,
                 econ: Optional[EconConfig] = None):
        assert validators, "need at least one validator"
        self.chain = chain
        self.store = store
        # token economy (repro.econ): on by default; per-round
        # settlement is host-side float arithmetic (no jit entry points)
        # committed to the chain's payout bulletin. ``roi`` is the
        # engine-local cost ledger (off-chain operating costs) the
        # attack-ROI profit curves fold against the chain balances.
        self.econ = econ if econ is not None else EconConfig()
        self.roi = PayoutLedger()
        # per-round, per-validator serialized settlements — replicas
        # must agree byte-for-byte (tests/test_econ.py pins this)
        self.settlements: Dict[int, Dict[str, str]] = {}
        # optional FlightRecorder (repro.obs): round records stream to
        # its SSE feed, metrics update per round, and the topology
        # endpoint reads this engine. Passive — the seeded round math
        # and the deterministic telemetry export are unchanged.
        self.obs = obs
        if obs is not None:
            obs.topology_fn = self.topology
        self.validators: Dict[str, Validator] = {v.uid: v
                                                 for v in validators}
        self.peers: Dict[str, PeerNode] = dict(peers)
        self._pending_joins: set = set()     # bootstrap downloads in flight
        self.offline_validators: set = set()
        self.telemetry = telemetry or Telemetry("adhoc", 0)
        self.grad_fn = grad_fn
        self.hp = validators[0].hp
        self.fast_set_size = fast_set_size
        self.eval_every = eval_every
        self.eval_batch_fn = eval_batch_fn
        self.multi = len(self.validators) > 1
        self.reports: Dict[str, List[RoundReport]] = {
            uid: [] for uid in self.validators}
        self.val_losses: List[float] = []
        self._queue: list = []           # (block, seq, fn) heap
        self._seq = 0
        self._rounds = 0                 # scenario default for run()
        if isinstance(store, SimBucketStore):
            store.scheduler = self.schedule_in

    # ------------------------------------------------------------ events
    def schedule_at(self, block: int, fn: Callable[[], None]) -> None:
        heapq.heappush(self._queue, (block, self._seq, fn))
        self._seq += 1

    def schedule_in(self, delay_blocks: int, fn: Callable[[], None]) -> None:
        self.schedule_at(self.chain.block + delay_blocks, fn)

    def schedule_round(self, round_idx: int, fn: Callable[[], None]) -> None:
        self.schedule_at(round_idx * self.chain.blocks_per_round, fn)

    def _drain(self, upto_block: int) -> None:
        while self._queue and self._queue[0][0] <= upto_block:
            _, _, fn = heapq.heappop(self._queue)
            fn()

    # ---------------------------------------------------- churn handlers
    def _join(self, spec: PeerSpec, instant: bool = False) -> None:
        if spec.uid in self.peers:
            return
        assert self.grad_fn is not None, "engine built without grad_fn"
        cp = self.validators[self.chain.checkpoint_pointer]
        net = getattr(self.store, "network", None)
        if not instant and net is not None:
            # the checkpoint download transits the joiner's link: its
            # replica exists only after bandwidth-proportional time, so
            # "bootstrapping" peers miss produce windows emergently
            ckpt_bytes = sum(int(np.asarray(leaf).nbytes)
                             for leaf in jax.tree.leaves(cp.params))
            delay = net.download_blocks(spec.uid, ckpt_bytes)
            if delay > 0:
                self.telemetry.log_event(self.chain.block, "bootstrap",
                                         f"{spec.uid}+{delay}b")
                self._pending_joins.add(spec.uid)
                self.schedule_in(delay,
                                 lambda: self._finish_join(spec))
                return
        self._pending_joins.discard(spec.uid)
        pc = PeerConfig(uid=spec.uid, behavior=spec.behavior,
                        data_multiplier=spec.data_multiplier,
                        desync_rounds=spec.desync_rounds,
                        desync_start=spec.desync_start,
                        copy_victim=spec.copy_victim)
        # a joiner bootstraps its replica from the canonical checkpoint
        self.peers[spec.uid] = PeerNode(pc, cp.params, cp.scheme,
                                        self.grad_fn, self.hp, self.chain,
                                        self.store, cp.data)
        self.telemetry.log_event(self.chain.block, "join", spec.uid)

    def _finish_join(self, spec: PeerSpec) -> None:
        """Deferred arm of a bandwidth-delayed bootstrap: only completes
        if the peer's scheduled leave has not fired in the meantime — a
        leaver must not be resurrected by its own in-flight download."""
        if spec.uid in self._pending_joins:
            self._join(spec, instant=True)

    def _leave(self, uid: str) -> None:
        # a leave while the bootstrap download is still in flight simply
        # abandons the download
        self._pending_joins.discard(uid)
        if uid not in self.peers:
            return
        self.chain.deregister_peer(uid)
        self.store.remove_bucket(uid)
        del self.peers[uid]
        self.telemetry.log_event(self.chain.block, "leave", uid)

    def _set_behavior(self, uid: str, behavior: str) -> None:
        node = self.peers.get(uid)
        if node is not None:
            node.set_behavior(behavior, self.chain.round_of())
            self.telemetry.log_event(self.chain.block, "behavior",
                                     f"{uid}->{behavior}")

    # ------------------------------------------------- validator up/down
    def _validator_down(self, uid: str) -> None:
        if uid in self.validators and uid not in self.offline_validators:
            self.offline_validators.add(uid)
            # prune the stale bulletin so consensus stops counting it
            self.chain.withdraw_weights(uid)
            self.telemetry.log_event(self.chain.block, "validator_down",
                                     uid)

    def _validator_up(self, uid: str) -> None:
        if uid not in self.offline_validators:
            return
        # resync the recovered replica from the *current* checkpoint
        # pointer (a survivor) BEFORE it can become the pointer again
        cp = self.validators.get(self.chain.checkpoint_pointer)
        v = self.validators[uid]
        if cp is not None and v is not cp:
            v.params, v.step = cp.params, cp.step
            v.current_top_g = list(cp.current_top_g)
        self.offline_validators.discard(uid)
        self._repoint_checkpoint()
        self.telemetry.log_event(self.chain.block, "validator_up", uid)

    def _active_validators(self) -> List[Validator]:
        return [v for uid, v in self.validators.items()
                if uid not in self.offline_validators]

    def _repoint_checkpoint(self) -> None:
        act = self._active_validators()
        if not act:
            return
        top = max(act, key=lambda v: self.chain.validators[v.uid].stake)
        if self.chain.checkpoint_pointer != top.uid:
            self.chain.set_checkpoint_pointer(top.uid)
            self.telemetry.log_event(self.chain.block, "checkpoint",
                                     f"->{top.uid}")

    def _validator_order(self) -> List[Validator]:
        """Checkpoint-pointer validator first (it publishes the baseline
        cache the others read), then by stake, then uid."""
        cp = self.chain.checkpoint_pointer
        return sorted(self._active_validators(),
                      key=lambda v: (v.uid != cp,
                                     -self.chain.validators[v.uid].stake,
                                     v.uid))

    # ------------------------------------------------------------ rounds
    def run_round(self, rnd: int) -> None:
        bpr = self.chain.blocks_per_round
        start, end = rnd * bpr, (rnd + 1) * bpr
        # snapshot BEFORE the boundary drain: an arrival landing exactly on
        # the round-start block belongs to this round's network delta
        net = getattr(self.store, "network", None)
        net_before = net.stats.as_dict() if net else None
        self._drain(start)               # joins/leaves/flips/failovers
        # --- peers publish; uploads may arrive later (or never)
        active = list(self.peers)
        for uid in active:
            node = self.peers.get(uid)
            if node is not None:
                node.produce(rnd)
        # --- the put window elapses block by block; arrivals land
        while self.chain.block < end:
            self.chain.advance(1)
            self._drain(min(self.chain.block, end - 1))
        # --- concurrent validator pipelines, composing each validator's
        # OWN stage list (custom/spliced stages keep working); the
        # pipeline is split at stage_aggregate so every validator posts
        # before anyone aggregates
        self._repoint_checkpoint()
        order = self._validator_order()
        ctxs, cuts = {}, {}
        for v in order:
            stages = list(v.stages)
            try:
                cut = stages.index(v.stage_aggregate)
            except ValueError:
                cut = len(stages)
            ctx = v.build_context(
                rnd, [u for u in active if u in self.chain.peers],
                fast_set_size=self.fast_set_size)
            v.begin_round_obs(ctx)
            for stage in stages[:cut]:         # ... incl. the chain post
                ctx = v.run_stage(stage, ctx)
            ctxs[v.uid], cuts[v.uid] = ctx, (stages, cut)
        # --- incentive resolves across validators by stake-weighted median
        consensus = self.chain.consensus_weights()
        if self.multi:
            # zero-consensus peers (audit-zeroed by the validator quorum)
            # must not be topped up to 1/G by rank ties; filtering on the
            # shared consensus keeps every replica bit-identical
            agg_weights = S.top_g_weights(
                {p: w for p, w in consensus.items() if w > 0},
                self.hp.top_g)
        else:
            agg_weights = ctxs[order[0].uid].weights if order else {}
        # --- coordinated aggregation: every replica applies the same rule
        lr = 0.0
        for v in order:
            ctx = ctxs[v.uid]
            if self.multi:
                ctx.weights = dict(agg_weights)
            stages, cut = cuts[v.uid]
            for stage in stages[cut:]:
                ctx = v.run_stage(stage, ctx)
            v.end_round_obs(ctx)
            ctxs[v.uid] = ctx
            lr = ctx.lr
            self.reports[v.uid].append(ctx.report())
            for uid, reason in sorted(ctx.audit_flagged.items()):
                self.telemetry.log_event(self.chain.block, "audit_flag",
                                         f"{v.uid}:{uid}:{reason}")
        for uid in active:
            node = self.peers.get(uid)
            if node is not None:
                node.apply_round(rnd, agg_weights, lr)
        econ_rec = self._settle(rnd, order, ctxs, consensus)
        self._record(rnd, active, ctxs, order, consensus, net, net_before,
                     econ_rec)

    def _settle(self, rnd, order, ctxs,
                consensus) -> Optional[Dict[str, Any]]:
        """Per-round token settlement (repro.econ): every replica folds
        the posted chain state into the same entry tuple, the first
        post commits it (``Chain.post_payouts``), and the engine debits
        the off-chain operating costs the attack-ROI curves need.
        Host-side float arithmetic only — no jit entry points, no
        per-round compiles."""
        ec = self.econ
        if not ec.enabled or not order:
            return None
        # quorum verdict sets: fresh flags and active strike bans,
        # unioned across validators (computed once, shared by every
        # replica's settlement — like the consensus weights themselves)
        flagged: Dict[str, str] = {}
        banned: set = set()
        for v in order:
            for uid, reason in sorted(ctxs[v.uid].audit_flagged.items()):
                flagged.setdefault(uid, reason)
            banned |= {u for u, n in v.audit_strikes.items() if n > 0}
        flagged = dict(sorted(flagged.items()))
        # every replica computes BEFORE anyone commits — committing
        # applies slash entries to live stake, and the settlement must
        # be a pure function of the *pre-settlement* chain state
        computed = {v.uid: settle_round(ec, self.chain, rnd,
                                        consensus=consensus,
                                        banned=banned, flagged=flagged)
                    for v in order}
        self.settlements[rnd] = {
            uid: json.dumps([e.to_dict() for e in entries],
                            sort_keys=True)
            for uid, entries in computed.items()}
        for v in order:                  # first write wins on chain
            self.chain.post_payouts(v.uid, rnd, computed[v.uid])
        # ---- off-chain operating costs (attack-ROI accounting)
        block = self.chain.block
        for uid in sorted(self.peers):
            node = self.peers[uid]
            cost = behavior_cost(ec, node.pc.behavior,
                                 node.pc.data_multiplier)
            if cost > 0:
                self.roi.debit(uid, cost, block=block, round_idx=rnd,
                               reason=f"cost:{node.pc.behavior}")
        # ---- telemetry view of the committed round
        payouts: Dict[str, float] = {}
        burned = slashed = 0.0
        for e in self.chain.payouts(rnd):
            if e.kind == "credit":
                payouts[e.uid] = payouts.get(e.uid, 0.0) + e.amount
            elif e.kind == "burn":
                burned += e.amount
            elif e.kind == "slash":
                slashed += e.amount
        balances = self.chain.balances()
        costs = self.roi.balances()
        profit = {uid: balances.get(uid, 0.0) + costs.get(uid, 0.0)
                  for uid in sorted(self.peers)}
        return {"emission": round_emission(ec, rnd),
                "payouts": dict(sorted(payouts.items())),
                "burned": burned, "slashed": slashed,
                "banned": sorted(banned),
                "balances": balances, "profit": profit,
                "supply": sum(balances.values())}

    def _record(self, rnd, active, ctxs, order, consensus, net,
                net_before, econ_rec=None) -> None:
        val_loss = None
        if (self.eval_batch_fn is not None and rnd % self.eval_every == 0
                and order):
            cp = self.validators[self.chain.checkpoint_pointer]
            val_loss = float(cp.eval_loss(cp.params,
                                          self.eval_batch_fn(rnd)))
            self.val_losses.append(val_loss)
            for v in order:
                self.reports[v.uid][-1].train_loss = val_loss
        behav = {uid: node.pc.behavior
                 for uid, node in self.peers.items()}
        total_w = sum(consensus.values())
        honest_w = sum(w for p, w in consensus.items()
                       if behav.get(p) in HONEST_BEHAVIORS)
        net_delta = None
        if net is not None:
            after = net.stats.as_dict()
            net_delta = {k: after[k] - net_before[k] for k in after}
        cp_uid = self.chain.checkpoint_pointer
        cp = self.validators.get(cp_uid)
        record = self.telemetry.record_round(
            round=rnd, block=self.chain.block,
            active_peers=sorted(self.peers),
            honest_share=(honest_w / total_w if total_w > 0 else 0.0),
            consensus=consensus,
            fast_pass_rate={
                v.uid: (sum(ctxs[v.uid].fast_pass.values())
                        / len(ctxs[v.uid].fast_pass)
                        if ctxs[v.uid].fast_pass else 1.0)
                for v in order},
            eval_counts={v.uid: len(ctxs[v.uid].eval_set) for v in order},
            mu={p: cp.peer_state[p].mu for p in sorted(self.peers)
                if cp and p in cp.peer_state},
            ordinals={p: cp.book.ordinal(p) for p in sorted(self.peers)}
            if cp else {},
            val_loss=val_loss, lr=(order and ctxs[order[0].uid].lr) or 0.0,
            checkpoint=cp_uid,
            offline_validators=sorted(self.offline_validators),
            network=net_delta,
            audit={v.uid: dict(sorted(ctxs[v.uid].audit_flagged.items()))
                   for v in order},
            # wall-clock per-stage breakdown: routed by Telemetry to its
            # ``perf`` side-channel, never into the deterministic record
            stage_ms={v.uid: {s: round(ms, 3) for s, ms
                              in v.last_stage_ms.items()}
                      for v in order},
            # token settlement view (repro.econ): absent when the
            # scenario runs with the economy disabled
            **({"econ": econ_rec} if econ_rec is not None else {}))
        if self.obs is not None:
            explains: List[Dict[str, Any]] = []
            for v in order:
                explains.extend(explain_round(
                    rnd, v, ctxs[v.uid], consensus=consensus,
                    behaviors=behav, econ=econ_rec).values())
            self.obs.publish_round(record, explains)

    # --------------------------------------------------------- topology
    def topology(self) -> Dict[str, Any]:
        """Live network topology for the daemon's
        ``/v1/system/topology`` endpoint: peers (behaviour + link),
        validators (stake, liveness, checkpoint role) and the chain
        clock. JSON-safe — infinite link bandwidths become None."""
        net = getattr(self.store, "network", None)

        def link(profile) -> Dict[str, Any]:
            return {k: (None if isinstance(v, float) and math.isinf(v)
                        else v)
                    for k, v in dataclasses.asdict(profile).items()}

        peers = {}
        for uid, node in sorted(self.peers.items()):
            peers[uid] = {
                "behavior": node.pc.behavior,
                "registered": uid in self.chain.peers,
                "link": link(net.profile(uid)) if net else None,
            }
        validators = {}
        for uid, v in sorted(self.validators.items()):
            validators[uid] = {
                "stake": self.chain.validators[uid].stake,
                "online": uid not in self.offline_validators,
                "checkpoint": uid == self.chain.checkpoint_pointer,
                "step": v.step,
                "peers_rated": len(v.peer_state),
            }
        return {
            "scenario": self.telemetry.scenario,
            "seed": self.telemetry.seed,
            "scheme": next(iter(self.validators.values())).scheme.name,
            "block": self.chain.block,
            "round": self.chain.round_of(),
            "blocks_per_round": self.chain.blocks_per_round,
            "default_link": link(net.default) if net else None,
            "peers": peers,
            "validators": validators,
            "pending_joins": sorted(self._pending_joins),
        }

    def run(self, num_rounds: Optional[int] = None) -> Telemetry:
        start = self.chain.round_of()
        n = num_rounds if num_rounds is not None else self._rounds
        for rnd in range(start, start + n):
            self.run_round(rnd)
        return self.telemetry

    # ------------------------------------------------------ construction
    @classmethod
    def from_scenario(cls, scenario: Scenario, cfg=None,
                      hp=None, *, batch: int = 4, seq_len: int = 64,
                      eval_batch: int = 8,
                      eval_every: Optional[int] = None,
                      blocks_per_round: int = 10,
                      eval_chunk: int = 0,
                      mesh_devices: int = 0,
                      obs=None) -> "SimEngine":
        """Wire a complete testnet from a declarative scenario.

        ``eval_chunk`` (ignored when ``hp`` is supplied) bounds each
        validator's primary-eval memory to that many dense deltas at a
        time — the knob for running wide eval sets on small validator
        hardware (see ``hp.eval_chunk``). ``scenario.scheme`` selects the
        gradient scheme (repro.schemes registry) when ``hp`` is not
        supplied; with an explicit ``hp``, ``hp.scheme`` wins.

        ``mesh_devices`` > 0 gives every validator a peer mesh over that
        many local devices (``launch.mesh.make_peer_mesh``): the round
        entry points shard their peer axis and an N-device validator
        scores ~N× peers per wall-clock round. Results are bit-identical
        to ``mesh_devices=0`` on one device. Compiled round programs
        persist across runs in the compilation cache
        (``launch.compile_cache``), so run 2 starts warm.

        ``obs`` (a :class:`repro.obs.FlightRecorder`) attaches the
        flight recorder to every validator and the engine: round/stage
        spans, metrics, verdict explains and the SSE round feed —
        without perturbing trace counts or the seeded telemetry."""
        from repro.configs.base import TrainConfig
        from repro.configs.registry import tiny_config
        from repro.data import pipeline
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.mesh import make_peer_mesh
        from repro.models import model as M
        from repro.schemes import make_scheme

        enable_compile_cache()
        mesh = make_peer_mesh(mesh_devices) if mesh_devices else None
        cfg = cfg or tiny_config()
        n_specs = len(scenario.peers)
        hp = hp or TrainConfig(
            seed=scenario.seed, learning_rate=3e-3, warmup_steps=2,
            total_steps=max(100, scenario.rounds),
            top_g=scenario.top_g or max(3, n_specs // 2),
            eval_set_size=scenario.eval_set_size or n_specs,
            demo_chunk=16, demo_topk=8, poc_gamma=0.6,
            eval_chunk=eval_chunk, scheme=scenario.scheme)
        corpus = pipeline.MarkovCorpus(cfg.vocab_size, seed=scenario.seed)
        chain = Chain(blocks_per_round=blocks_per_round,
                      genesis_seed=scenario.seed)
        network = NetworkModel(seed=scenario.seed)
        store = SimBucketStore(chain, network)
        # assignments derive from the chain block hash (auditable,
        # commit-then-reveal — repro.audit.assignment)
        data_fns = audit_assignment.chain_data_fns(corpus, chain, hp.seed,
                                                   batch, seq_len)
        params = M.init_params(cfg, jax.random.PRNGKey(hp.seed))
        scheme = make_scheme(hp, params)
        eval_loss = jax.jit(lambda p, b: M.loss_fn(p, b, cfg)[0])

        def grad_fn(p, b):
            return jax.grad(lambda pp: M.loss_fn(pp, b, cfg)[0])(p)

        cache = BaselineCache() if len(scenario.validators) > 1 else None
        validators = [
            Validator(vs.uid, params, scheme, eval_loss, hp, chain, store,
                      data_fns, stake=vs.stake,
                      rng=np.random.RandomState(
                          (scenario.seed * 7919
                           + zlib.crc32(vs.uid.encode())) % (2 ** 31)),
                      baseline_cache=cache, grad_fn=grad_fn, mesh=mesh,
                      obs=obs)
            for vs in scenario.validators]
        telemetry = Telemetry(scenario.name, scenario.seed, meta={
            "model": cfg.name, "params": cfg.param_count(),
            "peers": n_specs, "validators": len(scenario.validators),
            "blocks_per_round": blocks_per_round, "scheme": scheme.name,
            "description": scenario.description})
        engine = cls(chain, store, validators, {}, telemetry=telemetry,
                     grad_fn=grad_fn, obs=obs, econ=scenario.econ,
                     eval_every=eval_every
                     or max(scenario.rounds // 6, 1),
                     eval_batch_fn=lambda rnd: pipeline.unassigned_data(
                         corpus, 99, "eval", rnd, eval_batch, seq_len))
        engine._rounds = scenario.rounds
        # resolve round-relative link specs against the real payload size
        payload_bytes = scheme.estimate_payload_bytes()
        network.default = scenario.default_link.resolve(payload_bytes,
                                                        blocks_per_round)
        for spec in scenario.peers:
            if spec.link is not None:
                network.links[spec.uid] = spec.link.resolve(
                    payload_bytes, blocks_per_round)
        # translate the declarative lifecycle into scheduled events
        for spec in scenario.peers:
            if spec.join_round <= 0:
                # genesis peers ARE the network: no checkpoint to fetch
                engine._join(spec, instant=True)
            else:
                engine.schedule_round(
                    spec.join_round,
                    lambda s=spec: engine._join(s))
            if spec.leave_round is not None:
                engine.schedule_round(
                    spec.leave_round,
                    lambda u=spec.uid: engine._leave(u))
            if spec.rejoin_round is not None:
                engine.schedule_round(
                    spec.rejoin_round,
                    lambda s=spec: engine._join(s))
            for when, behavior in spec.behavior_schedule:
                engine.schedule_round(
                    when,
                    lambda u=spec.uid, b=behavior:
                    engine._set_behavior(u, b))
        for vs in scenario.validators:
            for down, up in vs.offline:
                engine.schedule_round(
                    down, lambda u=vs.uid: engine._validator_down(u))
                engine.schedule_round(
                    up, lambda u=vs.uid: engine._validator_up(u))
        return engine
