"""The Gauntlet validator (paper §3, Algorithm 1) as composable round stages.

Round architecture
------------------
A communication round is a pipeline of four stages that communicate only
through an explicit :class:`RoundContext` blackboard:

``fast-filter``
    Large set F_t (top-G always included, §3.3): put-window, format and
    sync-score checks; applies the φ penalty on failure and caches every
    fetched payload on the context so later stages never re-fetch. The
    sync-score math for the whole filter set is **vectorized** into one
    jitted call over a (|F_t|, N) sample matrix — only the bucket reads
    and format checks remain host-side per peer.

``uniqueness``
    Proof-of-unique-work audit (``repro.audit``): chain-commitment
    checks of the consumed-batch digests, one jitted count-sketch
    fingerprint + pairwise-similarity call over the stacked eval set
    (verbatim / delayed / noise-masked copy detection against this and
    the previous round), and replay audits — spot checks of k sampled
    peers plus arbitration inside similarity clusters, recomputing local
    steps with the peers' own shared jitted program. Flags zero the
    round score (scoreboard stage) and demote the OpenSkill rating.

``primary-eval``
    Small set S_t: **batched** LossScore (eq. 2). The eval set's payloads
    are stacked once along a leading peer axis
    (:meth:`repro.schemes.GradScheme.stack_payloads`), the signed per-peer
    deltas and the stepped-parameter losses are ``vmap``-ed over that axis,
    and the baseline losses L(θ, D) are computed once per *unique* batch
    (deduplicated within the assigned and within the random stack — their
    shapes may differ) then gathered back per peer — O(1) compiled calls
    per round instead of the 4·|S_t| dispatches of the per-peer loop. Baselines live in their own jitted
    entry point so redundant validators can skip them entirely: with a
    shared :class:`BaselineCache`, the chain's checkpoint-pointer validator
    computes and publishes L(θ_step, D) per (step, batch digest) and every
    other validator reads the cache instead of recomputing (the ROADMAP
    multi-validator dedupe follow-up — asserted via per-validator
    ``baseline_calls`` / ``compiled_calls`` in ``benchmarks/sim_bench.py``).

``scoreboard``
    Proof-of-computation μ update (batched eq. 3), OpenSkill LossRating
    match, PEERSCORE (eq. 4), eq.-5 normalization, the on-chain weight
    post, and the top-G weights (eq. 6).

``aggregate``
    Coordinated scheme update of the global model. Contributors already
    present in the stacked eval-set payloads are reused by gathering their
    rows *inside* the jitted aggregator
    (:meth:`repro.schemes.GradScheme.aggregate_apply`) — no re-fetch and
    no re-stack; the parameter update is fused into the same compiled call.

Scheme-agnostic by construction: everything payload-shaped — the wire
format, format validation, the dense signed delta a LossScore evaluates,
stacking/padding, aggregation and the audit's sketch flattening — goes
through the :class:`repro.schemes.GradScheme` object the validator is
constructed with (``hp.scheme`` selects it); the Gauntlet itself never
touches a payload field.

:meth:`Validator.run_round` composes ``self.stages`` in order; callers may
reorder, drop or substitute stages (benchmarks time individual stages,
tests drive them one at a time). ``Validator.compiled_calls`` counts
invocations of the batched jit entry points — sync-scores, audit
fingerprint, baselines, primary scores, aggregate (5), plus the batched
replay audit (one assigned + one decoy dispatch and their sketches,
regardless of how many peers are audited). The per-round dispatch count
is therefore O(1) in the peer count, which
``benchmarks/gauntlet_bench.py`` measures at 8→64 peers (baselines drop
to 0 on a full cache hit, partial hits recompute only missing rows).

Static shapes / bounded memory
------------------------------
Every data-dependent axis a jitted entry point sees — the |S_t| peer
stack, the |F_t| sync samples, the unique-batch stacks, the baseline
missing-row vectors, the fingerprint reference window and the
aggregation rows — is padded to a **sticky power-of-two bucket**
(:mod:`repro.core.padding`, knobs ``hp.eval_pad_min`` /
``hp.eval_pad_cap``) with validity masks or row counts threaded through
the call, so each entry point compiles **once per run** even as churn
wobbles the live sizes (``Validator.trace_counts`` /
:meth:`Validator.trace_counts_all` count retraces; the retrace-
regression test and ``BENCH_gauntlet.json`` pin them flat). Padded rows
are exact no-ops: zero payloads decompress to zero deltas, masked
scores multiply to 0.0, and zero aggregation weights turn padded
contributions into ±0.0 adds — results are bit-identical to the
unpadded path. With ``hp.eval_chunk`` > 0 the primary eval additionally
runs ``lax.map`` over vmap blocks of that many peers with
decompress→sign→step→loss fused inside each block, bounding peak live
memory at O(eval_chunk × params) instead of materializing all |S_t|
dense deltas at once (:meth:`Validator.primary_memory_analysis`
measures the difference without executing). The unique-batch baseline
stacks stream through the same ``lax.map`` chunking.

Multi-device rounds
-------------------
Constructed with ``mesh=`` (a 1-axis peer mesh from
:func:`repro.launch.mesh.make_peer_mesh`), the validator shard_maps its
row-parallel entry points — primary eval, baselines, sync scores,
fingerprint sketches and the batched replay audit — over
``sharding.PEER_AXIS``: each device scores its slice of the padded peer
bucket, so an N-device validator covers ~N× the peers per wall-clock
round. Every sticky bucket is additionally padded to a multiple of the
mesh size (times any chunk multiple — see
:class:`repro.core.padding.BucketTracker`), so shards divide evenly and
the masked rows stay exact no-ops. Only the fingerprint stage needs a
collective (one tiled ``all_gather`` of the K×fingerprint_dim sketch
rows before the pairwise cosine); aggregation stays unsharded — it is
the fleet-shared program peer replicas run bit-identically. A 1-device
mesh lowers the exact same math and reproduces the no-mesh path
bit-for-bit (tests/test_gauntlet_mesh.py pins this).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.audit import assignment, fingerprint
from repro.audit.replay import ReplayAuditor
from repro.comms.bucket import BucketStore
from repro.comms.chain import Chain
from repro.configs.base import TrainConfig
from repro.core import padding, scores as S
from repro.core.openskill import RatingBook
from repro.demo.schedules import warmup_cosine
from repro.schemes import GradScheme


# how many recent evaluated rounds of sketches the delayed-copy check
# compares against (bridges rounds where the eval set came up empty)
AUDIT_REF_ROUNDS = 2


@dataclasses.dataclass
class PeerState:
    mu: float = 0.0                 # proof-of-computation EMA (eq. 3)
    last_fast_pass: bool = True
    evals: int = 0


@dataclasses.dataclass
class RoundReport:
    round_idx: int
    evaluated: List[str]
    fast_checked: List[str]
    loss_scores_rand: Dict[str, float]
    loss_scores_assigned: Dict[str, float]
    norm_scores: Dict[str, float]
    weights: Dict[str, float]
    lr: float
    train_loss: Optional[float] = None
    audit_flagged: Dict[str, str] = dataclasses.field(default_factory=dict)
    # uniqueness-stage diagnostics: similarity clusters + replay margins
    audit_detail: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class RoundContext:
    """Mutable blackboard threaded through the round stages.

    Each stage reads what earlier stages produced and writes its own
    outputs; nothing else is shared between stages, so any stage can be
    run (or replaced) in isolation given a suitably-populated context.
    """
    round_idx: int
    active_peers: List[str]
    fast_set_size: Optional[int] = None
    # fast-filter →
    fast_set: List[str] = dataclasses.field(default_factory=list)
    fast_pass: Dict[str, bool] = dataclasses.field(default_factory=dict)
    payloads: Dict[str, Any] = dataclasses.field(default_factory=dict)
    sync_samples: Dict[str, Any] = dataclasses.field(
        default_factory=dict)   # raw prefetched sync objects (fast filter)
    # uniqueness / primary-eval → (the eval set is selected by whichever
    # of the two stages runs first; both share the stacked payloads)
    eval_set: List[str] = dataclasses.field(default_factory=list)
    eval_selected: bool = False
    # Payload tree; rows [0, len(eval_set)) follow eval order, the rest
    # is zero padding up to the validator's sticky peer bucket
    stacked_payloads: Any = None
    stacked_index: Dict[str, int] = dataclasses.field(default_factory=dict)
    assigned_batches: Dict[str, Any] = dataclasses.field(
        default_factory=dict)   # per-eval-peer SelectData cache
    unassigned_batches: Dict[str, Any] = dataclasses.field(
        default_factory=dict)   # per-eval-peer random-subset cache
    # uniqueness →
    audit_flagged: Dict[str, str] = dataclasses.field(
        default_factory=dict)   # uid -> reason (this round's fresh flags)
    audit: Dict[str, Any] = dataclasses.field(default_factory=dict)
    loss_scores_assigned: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    loss_scores_rand: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    # scoreboard →
    norm_scores: Dict[str, float] = dataclasses.field(default_factory=dict)
    weights: Dict[str, float] = dataclasses.field(default_factory=dict)
    # aggregate →
    contributors: List[str] = dataclasses.field(default_factory=list)
    lr: float = 0.0
    train_loss: Optional[float] = None

    def report(self) -> RoundReport:
        return RoundReport(round_idx=self.round_idx,
                           evaluated=list(self.eval_set),
                           fast_checked=list(self.fast_set),
                           loss_scores_rand=dict(self.loss_scores_rand),
                           loss_scores_assigned=dict(
                               self.loss_scores_assigned),
                           norm_scores=dict(self.norm_scores),
                           weights=dict(self.weights), lr=self.lr,
                           train_loss=self.train_loss,
                           audit_flagged=dict(self.audit_flagged),
                           audit_detail=dict(self.audit))


def eligible_contributors(weights: Dict[str, float], store: BucketStore,
                          chain: Chain, round_idx: int) -> List[str]:
    """§3.3: only positive-weight peers whose payload landed inside the put
    window may be aggregated. Validator and every peer apply this same rule
    (via this same function) — otherwise replicas drift from θ^validator."""
    return [p for p, w in weights.items()
            if w > 0 and store.within_put_window(p, round_idx,
                                                 chain.blocks_per_round)]


def _batch_key(batch) -> bytes:
    """Content digest of a data batch — the baseline-loss cache key AND
    the commit-then-reveal digest (one canonical construction, in
    :func:`repro.audit.assignment.batch_digest`)."""
    return assignment.batch_digest(batch)


def _stack_batches(batches: List[Any]):
    """List of identically-shaped batch pytrees -> leading axis K."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)


def _payload_rows(stacked) -> int:
    """Leading (peer) axis length of a stacked payload tree (any scheme:
    every array leaf of a stacked payload carries the peer axis first)."""
    return jax.tree.leaves(stacked)[0].shape[0]


def _unique_batches(batches: List[Any]):
    """Deduplicate a list of batches by content.

    Returns (unique_batches, index, keys): ``index[i]`` is the row of
    ``batches[i]`` inside ``unique_batches`` — peers sharing an eval batch
    share one baseline-loss evaluation — and ``keys[j]`` is the content
    digest of ``unique_batches[j]`` (the :class:`BaselineCache` key, so the
    same dedup extends across validators).
    """
    slots: Dict[bytes, int] = {}
    uniq, index, keys = [], [], []
    for b in batches:
        k = _batch_key(b)
        if k not in slots:
            slots[k] = len(uniq)
            uniq.append(b)
            keys.append(k)
        index.append(slots[k])
    return uniq, np.asarray(index, np.int32), keys


class BaselineCache:
    """Cross-validator bulletin of baseline losses L(θ_step, D).

    Redundant validators evaluate the *same* peers on the *same*
    deterministic batches against bit-identical replicas of θ, so their
    baseline losses are pure duplicates. The validator named by the
    chain's ``checkpoint_pointer`` publishes its baselines per
    (model step, batch digest); the others look them up and skip the
    baseline compiled call entirely. Only the current step is retained —
    θ changes every aggregation, so older entries can never hit.

    Lookup is per key: a replica whose eval set only partially overlaps
    the pointer's reads the overlapping baselines and computes just the
    missing ones (``stage_primary_eval`` slices the unique-batch stack
    down to the misses — the ROADMAP partial-reuse follow-up). When the
    key sets coincide (the ``SimEngine.from_scenario`` default, where
    ``eval_set_size`` covers the in-window candidates) replicas issue
    zero baseline compiled calls.
    """

    def __init__(self):
        self._step: Optional[int] = None
        self._vals: Dict[bytes, float] = {}
        self.hits = 0          # lookups fully served from the cache
        self.partial_hits = 0  # lookups that saved at least one key
        self.misses = 0        # lookups that had to compute something

    def publish(self, step: int, keys: List[bytes], values) -> None:
        if step != self._step:
            self._step, self._vals = step, {}
        for k, v in zip(keys, values):
            self._vals[k] = float(v)

    def lookup_partial(self, step: int,
                       keys: List[bytes]) -> Dict[bytes, float]:
        """Per-key baselines for ``step``: whatever subset is known."""
        if step != self._step:
            self.misses += 1
            return {}
        found = {k: self._vals[k] for k in keys if k in self._vals}
        if len(found) == len(keys):
            self.hits += 1
        else:
            self.misses += 1
            if found:
                self.partial_hits += 1
        return found

    def lookup(self, step: int, keys: List[bytes]):
        """All-or-nothing view over :meth:`lookup_partial` (legacy API)."""
        found = self.lookup_partial(step, keys)
        if len(found) != len(keys):
            return None
        return [found[k] for k in keys]


class Validator:
    """Holds the reference model θ and runs Algorithm 1 every round."""

    def __init__(self, uid: str, params, scheme: GradScheme,
                 eval_loss_fn: Callable,
                 hp: TrainConfig, chain: Chain, store: BucketStore,
                 data_fns: Dict[str, Callable], stake: float = 1000.0,
                 rng: Optional[np.random.RandomState] = None,
                 baseline_cache: Optional[BaselineCache] = None,
                 grad_fn: Optional[Callable] = None,
                 mesh=None, obs=None):
        from repro import sharding as shd   # pulls in model modules
        self.uid = uid
        # optional FlightRecorder (repro.obs): round/stage/dispatch
        # spans + per-round metric deltas. Strictly passive — None (the
        # default) and an attached recorder run the identical round math
        self.obs = obs
        self._round_span = None
        self.params = params
        self.scheme = scheme
        self.eval_loss = eval_loss_fn          # (params, batch) -> scalar
        self.hp = hp
        self.chain = chain
        self.store = store
        # data_fns: assigned(peer, round) / unassigned(peer, round)
        self.data = data_fns
        self.rng = rng or np.random.RandomState(0)
        self.book = RatingBook(mu=hp.openskill_mu, sigma=hp.openskill_sigma,
                               beta=hp.openskill_beta, kappa=hp.openskill_kappa)
        self.peer_state: Dict[str, PeerState] = {}
        self.step = 0
        self.current_top_g: List[str] = []
        self.compiled_calls = 0        # batched jit-entry invocations
        self.last_stage_ms: Dict[str, float] = {}  # per-stage wall ms of
                                       # the most recent run_stages call
        self.baseline_calls = 0        # baseline-loss invocations (cacheable)
        self.baseline_rows = 0         # unique batches actually evaluated
        self.baseline_cache = baseline_cache
        self._last_fast_check: Dict[str, int] = {}
        # optional 1-axis peer mesh: row-parallel entry points shard
        # their peer axis over it (module docstring, "Multi-device
        # rounds"); None keeps the single-device path byte-for-byte
        self.mesh = mesh
        self._peer_axis = shd.PEER_AXIS
        self._mesh_n = shd.peer_mesh_size(mesh)
        # sticky power-of-two padding buckets per data-dependent axis:
        # once a run has seen its high-water mark, every jitted entry
        # point below holds ONE compiled shape across churn. Mesh runs
        # fold the device count into every bucket so shards divide evenly
        self._pad = padding.BucketTracker(minimum=hp.eval_pad_min,
                                          cap=hp.eval_pad_cap,
                                          multiple=self._mesh_n)
        # aggregation is NOT row-sharded: its program is shared fleet-wide
        # with (possibly mesh-less) peer replicas, so its buckets must not
        # fold in the device count or a 3-device validator would disagree
        # with its replicas on the compiled aggregate shape
        self._agg_pad = padding.BucketTracker(minimum=hp.eval_pad_min,
                                              cap=hp.eval_pad_cap)
        # traces per entry point: the wrapped impl bodies only run when
        # XLA (re)traces, so these are compile counts, not dispatches
        self.trace_counts: collections.Counter = collections.Counter()
        self._primary_arg_spec = None  # ShapeDtypeStructs of the last call
        self._baseline_arg_spec = None
        chain.register_validator(uid, stake)
        # ---- proof-of-unique-work audit state (repro.audit) ----
        # replay audits need the training grad_fn; without it the stage
        # still runs commitment + fingerprint checks and falls back to
        # earliest-upload-wins inside similarity clusters
        self._replayer = (ReplayAuditor(grad_fn, scheme, hp, params,
                                        mesh=mesh)
                          if grad_fn is not None else None)
        self.audit_strikes: Dict[str, int] = {}   # uid -> rounds left zeroed
        # rolling (uids, sketches) of the last AUDIT_REF_ROUNDS evaluated
        # rounds — a window, not just round t-1, so a delayed copy still
        # matches its victim across an empty-eval round in between
        self._prev_sketches: List[tuple] = []
        # sketch hash seeded from the chain hash of a block AFTER genesis
        # registration closes (AuditConfig.sketch_seed_block), not from a
        # static/genesis seed. Resolution is LAZY (first audit stage, by
        # which point the block exists): on a live chain a future block's
        # hash cannot be fetched at construction, and eager resolution
        # would quietly reintroduce the offline-predictable seed this
        # defends against. (This stub chain's hashes are pure functions
        # of genesis, so the unpredictability is only as real as the
        # chain's — the seam is what a live deployment inherits.) Fixed
        # for the run so sketches stay comparable across rounds
        # (delayed-copy detection), identical across validators on one
        # chain.
        self._sketch_seed_block = self.audit_cfg.resolved_seed_block(
            chain.blocks_per_round)
        self._sketch_seed_cache: Optional[int] = None
        self._audit_rng_cache: Optional[np.random.RandomState] = None
        # the composable round pipeline — callers may substitute stages
        self.stages: List[Callable[[RoundContext], RoundContext]] = [
            self.stage_fast_filter, self.stage_uniqueness,
            self.stage_primary_eval, self.stage_scoreboard,
            self.stage_aggregate]
        # row-parallel entry points: with a mesh, wrap the impl in a
        # shard_map that splits the listed arg positions (and every
        # output) by rows over the peer axis; without one, jit the impl
        # directly — the same trace as before this knob existed
        def rows(fn, row_args):
            return fn if mesh is None else shd.shard_map_rows(
                mesh, fn, row_args)
        self._primary = jax.jit(self._traced("primary", rows(
            functools.partial(self._primary_scores, hp.eval_chunk),
            (1, 4, 5, 9))))
        self._baselines = jax.jit(self._traced("baselines", rows(
            functools.partial(self._baselines_impl, hp.eval_chunk),
            (3, 4))))
        self._sync_scores = jax.jit(self._traced("sync_scores", rows(
            self._sync_scores_impl, (1,))))
        # fingerprint is the one stage needing a collective (pairwise
        # cosine reads every row), so it gets a bespoke mesh variant
        self._fingerprint = jax.jit(self._traced(
            "fingerprint", self._fingerprint_impl if mesh is None
            else self._fingerprint_mesh))
        self._sketch = jax.jit(self._traced("sketch", rows(
            self._sketch_impl, (0,))))
        # the SAME compiled aggregate program every peer replica uses —
        # bit-identity by construction, one compile per shape fleet-wide
        self._agg = scheme.shared_aggregate_apply(params)
        if obs is not None:
            obs.attach_validator(self)

    # ------------------------------------------------------------ pieces
    @property
    def audit_cfg(self):
        """The audit knobs as one structured object (AuditConfig) —
        derived from ``self.hp`` on read, so benchmarks/tests that swap
        ``hp`` (e.g. audit on/off comparisons) take effect immediately."""
        return self.hp.audit

    @property
    def _sketch_seed(self) -> int:
        """Per-run count-sketch seed, resolved lazily from the chain
        hash of the post-registration block (see ``__init__``)."""
        if self._sketch_seed_cache is None:
            self._sketch_seed_cache = int.from_bytes(
                self.chain.block_hash(self._sketch_seed_block)[:4],
                "little")
        return self._sketch_seed_cache

    @property
    def _audit_rng(self) -> np.random.RandomState:
        """Spot-check / cluster-sampling RNG; folds the sketch seed in,
        so it shares the seed's lazy post-registration resolution."""
        if self._audit_rng_cache is None:
            self._audit_rng_cache = np.random.RandomState(
                (self.hp.seed * 1_000_003 + self._sketch_seed)
                % (2 ** 31))
        return self._audit_rng_cache

    def _traced(self, name: str, fn: Callable) -> Callable:
        """Wrap a jit impl so its Python body bumps ``trace_counts`` —
        the body only executes when XLA (re)traces, so the counter is
        the compile count for that entry point (the retrace-regression
        test and the bench assert it stays flat across churn). The
        wrapper takes the entry point's name, so each one compiles to
        its own ``jit_validator_<name>`` module in a profiler trace."""
        def wrapped(*args):
            self.trace_counts[name] += 1
            return fn(*args)
        wrapped.__name__ = wrapped.__qualname__ = f"validator_{name}"
        return wrapped

    def _baselines_impl(self, chunk, params, uniq_a, uniq_r,
                        rows_a, rows_r):
        """Baseline losses L(θ, D) for the requested rows of the round's
        padded unique assigned / unassigned batch stacks (separate
        stacks — their shapes may differ), in one compiled call. The row
        vectors are padded to the same sticky bucket as the stacks, so
        this entry point keeps one shape while the missing-row count
        wobbles with cache hits; padded rows re-score row 0 and are
        sliced away host-side. This is the part of primary eval that is
        identical across redundant validators, hence its own jit entry
        point (skippable on a :class:`BaselineCache` hit).

        ``chunk`` (static, = ``hp.eval_chunk``) bounds memory the same
        way it bounds primary eval: > 0 streams the row gathers through
        ``lax.map`` over vmap blocks of ``chunk`` batches, so at most
        ``chunk`` forward activations are live instead of the whole
        unique-batch bucket's."""
        def one_stack(uniq, rows):
            n = rows.shape[0]
            if chunk and chunk < n:
                blocks = n // chunk
                part = rows.reshape(blocks, chunk)
                return jax.lax.map(
                    lambda r: jax.vmap(
                        lambda b: self.eval_loss(params, b))(
                            jax.tree.map(lambda u: u[r], uniq)),
                    part).reshape(n)
            sel = jax.tree.map(lambda u: u[rows], uniq)
            return jax.vmap(lambda b: self.eval_loss(params, b))(sel)
        return one_stack(uniq_a, rows_a), one_stack(uniq_r, rows_r)

    def _primary_scores(self, chunk, params, stacked, uniq_a, uniq_r,
                        idx_a, idx_r, base_a, base_r, beta, valid):
        """One compiled call for the whole (padded) eval stack: signed
        deltas and stepped losses (eq. 2) against precomputed baselines.

        Only the *unique* batches are staged to the device; the per-peer
        views (and their baselines) are gathered via idx_a/idx_r inside
        the trace, and ``valid`` zeroes the padded rows' scores.

        ``chunk`` is static. 0 vmaps the whole peer axis at once —
        every dense params-sized delta is live simultaneously. > 0 runs
        ``lax.map`` over vmap blocks of ``chunk`` peers with
        decompress→sign→step→loss fused inside each block, so at most
        ``chunk`` dense deltas exist at any point: peak live memory is
        O(chunk × params) instead of O(|S_t| × params)
        (:meth:`primary_memory_analysis` measures both)."""
        def block(pl, ia, ir, vm):
            deltas = jax.vmap(self.scheme.single_peer_delta)(pl)
            s_a = S.batched_loss_scores(
                self.eval_loss, params, deltas,
                jax.tree.map(lambda u: u[ia], uniq_a), beta,
                baseline=base_a[ia], valid=vm)
            s_r = S.batched_loss_scores(
                self.eval_loss, params, deltas,
                jax.tree.map(lambda u: u[ir], uniq_r), beta,
                baseline=base_r[ir], valid=vm)
            return s_a, s_r

        peers = idx_a.shape[0]
        if chunk and chunk < peers:
            blocks = peers // chunk

            def part(x):
                return x.reshape((blocks, chunk) + x.shape[1:])
            s_a, s_r = jax.lax.map(
                lambda xs: block(*xs),
                (jax.tree.map(part, stacked), part(idx_a), part(idx_r),
                 part(valid)))
            return s_a.reshape(peers), s_r.reshape(peers)
        return block(stacked, idx_a, idx_r, valid)

    def _fingerprint_impl(self, stacked, ref):
        """One compiled call for the whole uniqueness fingerprint: sketch
        every eval-set payload, compare all pairs within the round AND
        against the previous round's (padded) sketches — verbatim,
        noise-masked and delayed copies all surface as high cosines. The
        scheme's ``flatten_for_sketch`` supplies (values, position-ids),
        so this entry point never assumes a payload layout."""
        sk = fingerprint.sketch_pairs(
            self.scheme.flatten_for_sketch(stacked),
            self.audit_cfg.fingerprint_dim, self._sketch_seed)
        return (sk, fingerprint.cosine_matrix(sk, sk),
                fingerprint.cosine_matrix(sk, ref))

    def _fingerprint_mesh(self, stacked, ref):
        """Mesh variant of :meth:`_fingerprint_impl`: each device
        sketches its row slice of the payload stack (the expensive,
        embarrassingly-parallel part), then ONE tiled all_gather shares
        the tiny (K, fingerprint_dim) sketch matrix so every device can
        compute its rows of the pairwise-cosine blocks. Row order is
        device order, so outputs concatenate back exactly like the
        single-device call."""
        ax = self._peer_axis

        def shard(stacked, ref):
            sk_loc = fingerprint.sketch_pairs(
                self.scheme.flatten_for_sketch(stacked),
                self.audit_cfg.fingerprint_dim, self._sketch_seed)
            sk = jax.lax.all_gather(sk_loc, ax, axis=0, tiled=True)
            return (sk, fingerprint.cosine_matrix(sk_loc, sk),
                    fingerprint.cosine_matrix(sk_loc, ref))

        return jax.shard_map(
            shard, mesh=self.mesh, in_specs=(P(ax), P()),
            out_specs=(P(), P(ax), P(ax)), axis_names={ax},
            check_vma=False)(stacked, ref)

    def _sketch_impl(self, stacked):
        """Sketches alone (replayed payloads get compared host-side)."""
        return fingerprint.sketch_pairs(
            self.scheme.flatten_for_sketch(stacked),
            self.audit_cfg.fingerprint_dim, self._sketch_seed)

    @staticmethod
    def _sync_scores_impl(ref, samples, alpha):
        """§3.2 sync scores for the whole filter set in one fused call:
        mean |θ^val_i − θ^peer_i| / α per row of the (K, N) sample
        matrix (the batched form of :func:`repro.core.scores.sync_score`)."""
        diff = jnp.abs(samples.astype(jnp.float32)
                       - ref.astype(jnp.float32)[None, :])
        return jnp.mean(diff, axis=1) / jnp.maximum(alpha, 1e-12)

    def _state(self, peer: str) -> PeerState:
        if peer not in self.peer_state:
            self.peer_state[peer] = PeerState()
        return self.peer_state[peer]

    def trace_counts_all(self) -> Dict[str, int]:
        """Compile counts per jitted entry point. The fleet-shared
        aggregate program cannot be wrapped (validator and peers fetch
        the same callable), so it reports its jit-cache size — every
        shape it has been compiled for, process-wide."""
        out = dict(self.trace_counts)
        out["aggregate"] = self._agg._cache_size()
        return out

    def primary_memory_analysis(
            self, eval_chunk: Optional[int] = None) -> Dict[str, int]:
        """AOT memory footprint of the primary entry point at the last
        round's operand shapes: lower + compile (no execution, no data)
        and read XLA's buffer assignment. ``eval_chunk`` overrides the
        configured chunking so benchmarks can compare the full-vmap and
        chunked peaks on identical operands. ``temp_bytes`` is the
        number to watch — it carries the live dense deltas."""
        if self._primary_arg_spec is None:
            return {}
        chunk = self.hp.eval_chunk if eval_chunk is None else eval_chunk
        fn = jax.jit(functools.partial(self._primary_scores, chunk))
        ma = fn.lower(*self._primary_arg_spec).compile().memory_analysis()
        temp = int(ma.temp_size_in_bytes)
        args = int(ma.argument_size_in_bytes)
        outs = int(ma.output_size_in_bytes)
        return {"temp_bytes": temp, "argument_bytes": args,
                "output_bytes": outs, "peak_bytes": temp + args + outs}

    def baseline_memory_analysis(
            self, eval_chunk: Optional[int] = None) -> Dict[str, int]:
        """AOT footprint of the baseline entry point (same protocol as
        :meth:`primary_memory_analysis`): ``eval_chunk`` compares the
        full-vmap and lax.map-streamed unique-batch stacks on the last
        round's operand shapes."""
        if self._baseline_arg_spec is None:
            return {}
        chunk = self.hp.eval_chunk if eval_chunk is None else eval_chunk
        fn = jax.jit(functools.partial(self._baselines_impl, chunk))
        ma = fn.lower(*self._baseline_arg_spec).compile().memory_analysis()
        temp = int(ma.temp_size_in_bytes)
        args = int(ma.argument_size_in_bytes)
        outs = int(ma.output_size_in_bytes)
        return {"temp_bytes": temp, "argument_bytes": args,
                "output_bytes": outs, "peak_bytes": temp + args + outs}

    def lr_at(self, step: Optional[int] = None) -> float:
        return float(warmup_cosine(step if step is not None else self.step,
                                   base_lr=self.hp.learning_rate,
                                   warmup_steps=self.hp.warmup_steps,
                                   total_steps=self.hp.total_steps))

    def _fetch_payload(self, ctx: RoundContext, peer: str):
        """Read a peer's payload once per round; cache on the context."""
        if peer in ctx.payloads:
            return ctx.payloads[peer]
        try:
            rk = self.chain.peers[peer].bucket_read_key
            payload, _ = self.store.get_gradient(peer, ctx.round_idx, rk)
        except Exception:
            return None
        ctx.payloads[peer] = payload
        return payload

    def _format_ok(self, payload) -> bool:
        """§3.2 check (c): structure, shapes, dtypes — the scheme owns
        its payload layout, so it owns the check."""
        return self.scheme.format_ok(payload)

    def _precheck(self, ctx: RoundContext, peer: str) -> bool:
        """§3.2 checks (a)-(c): put window, payload present, format."""
        if not self.store.within_put_window(
                peer, ctx.round_idx, self.chain.blocks_per_round):
            return False
        payload = self._fetch_payload(ctx, peer)
        return payload is not None and self._format_ok(payload)

    def _sync_sample(self, ctx: RoundContext, peer: str,
                     sync_ref: np.ndarray) -> Optional[np.ndarray]:
        """Fetch + validate the peer's published sync sample (served from
        the context's prefetch cache when the fast filter overlapped the
        bucket reads). A missing OR malformed sample (wrong shape/dtype)
        is the peer's failure, never the round's — Byzantine peers must
        not be able to abort evaluation for everyone else — so any
        problem degrades to None."""
        try:
            sample = ctx.sync_samples.get(peer)
            if sample is None:
                rk = self.chain.peers[peer].bucket_read_key
                sample, _ = self.store.buckets[peer].get(
                    f"sync/round-{ctx.round_idx:08d}", rk)
            arr = np.asarray(sample, np.float32)
        except Exception:
            return None
        if arr.shape != np.asarray(sync_ref).shape:
            return None
        return arr

    def _prefetch_reads(self, ctx: RoundContext,
                        peers: List[str]) -> None:
        """Overlap the fast filter's per-peer bucket reads (payload +
        sync sample) with a small thread pool for large F_t. Threads
        only perform the raw store reads; every decision that consumes
        them runs on the main thread in fast-set order, so the outcome
        is identical to the sequential path (ROADMAP async-prefetch
        follow-up)."""
        workers = self.hp.fast_prefetch_workers
        targets = [p for p in peers if p not in ctx.payloads]
        if workers <= 1 or len(targets) < 2 * workers:
            return
        sync_key = f"sync/round-{ctx.round_idx:08d}"

        def read(peer):
            payload = sample = None
            try:
                rk = self.chain.peers[peer].bucket_read_key
                payload, _ = self.store.get_gradient(peer, ctx.round_idx,
                                                     rk)
            except Exception:
                payload = None
            try:
                rk = self.chain.peers[peer].bucket_read_key
                sample, _ = self.store.buckets[peer].get(sync_key, rk)
            except Exception:
                sample = None
            return payload, sample

        with ThreadPoolExecutor(max_workers=workers) as ex:
            fetched = list(ex.map(read, targets))
        for peer, (payload, sample) in zip(targets, fetched):
            if payload is not None:
                ctx.payloads.setdefault(peer, payload)
            if sample is not None:
                ctx.sync_samples.setdefault(peer, sample)

    def _fast_check(self, ctx: RoundContext, peer: str,
                    sync_ref: np.ndarray) -> bool:
        """§3.2 checks (a)-(c) + sync score; pure predicate, no penalty.
        Scalar reference path — the round pipeline batches the sync-score
        math across F_t in :meth:`stage_fast_filter`."""
        if not self._precheck(ctx, peer):
            return False
        sample = self._sync_sample(ctx, peer, sync_ref)
        if sample is None:
            return False
        sc = S.sync_score(sync_ref, sample, self.lr_at())
        return sc <= self.hp.sync_score_threshold

    def fast_evaluate(self, peer: str, round_idx: int) -> bool:
        """Single-peer fast eval (φ penalty on fail, §3.2). The round
        pipeline batches this via :meth:`stage_fast_filter`."""
        ctx = RoundContext(round_idx=round_idx, active_peers=[peer])
        sync_ref = S.sample_params_for_sync(self.params,
                                            jax.random.PRNGKey(round_idx))
        ok = self._fast_check(ctx, peer, sync_ref)
        self._last_fast_check[peer] = round_idx
        st = self._state(peer)
        if not ok:
            st.mu *= self.hp.fast_eval_penalty
        st.last_fast_pass = ok
        return ok

    def primary_evaluate(self, peer: str, round_idx: int):
        """Scalar reference path for one peer (Algorithm 1 inner loop).

        The round pipeline uses the batched :meth:`stage_primary_eval`;
        this stays as the numerical oracle the batched path is regression
        tested against. Side-effect free (μ updates live in the
        scoreboard stage).
        """
        rk = self.chain.peers[peer].bucket_read_key
        payload, _ = self.store.get_gradient(peer, round_idx, rk)
        delta = self.scheme.single_peer_delta(payload)
        beta = self.hp.eval_beta_frac * self.lr_at()
        d_assigned = self.data["assigned"](peer, round_idx)
        d_rand = self.data["unassigned"](peer, round_idx)
        s_assigned = S.loss_score(self.eval_loss, self.params, delta,
                                  d_assigned, beta)
        s_rand = S.loss_score(self.eval_loss, self.params, delta,
                              d_rand, beta)
        return s_assigned, s_rand

    # ------------------------------------------------------------ stages
    def stage_fast_filter(self, ctx: RoundContext) -> RoundContext:
        """Fast evaluation over F_t: top-G always included (§3.3), the
        rest filled least-recently-checked-first (random among equals) so
        every active peer keeps getting coverage."""
        hp = self.hp
        fast_n = ctx.fast_set_size or max(len(ctx.active_peers) // 2,
                                          hp.top_g + 1)
        pool = [p for p in ctx.active_peers if p not in self.current_top_g]
        self.rng.shuffle(pool)
        pool.sort(key=lambda p: self._last_fast_check.get(p, -1))
        fast_set = (self.current_top_g
                    + pool[:max(0, fast_n - len(self.current_top_g))])
        sync_ref = S.sample_params_for_sync(
            self.params, jax.random.PRNGKey(ctx.round_idx))
        # host-side per peer: bucket reads + format checks (reads overlap
        # via the thread-pool prefetch for large F_t); the sync-score
        # math itself is batched below into one compiled call for all of F_t
        self._prefetch_reads(ctx, fast_set)
        samples, sampled_peers = [], []
        for peer in fast_set:
            if not self._precheck(ctx, peer):
                continue
            sample = self._sync_sample(ctx, peer, sync_ref)
            if sample is not None:
                samples.append(sample)
                sampled_peers.append(peer)
        passed: Dict[str, bool] = {}
        if samples:
            # pad rows to the sticky bucket: the sample count varies
            # round to round under churn/lossy networks, and an exact-K
            # shape would retrace every time it changes
            k = len(samples)
            mat = padding.pad_rows(samples, samples[0].size,
                                   bucket=self._pad.get("sync", k))
            scores = np.asarray(self._obs_dispatch(
                "sync_scores", self._sync_scores,
                jnp.asarray(sync_ref), jnp.asarray(mat),
                jnp.float32(self.lr_at())))[:k]
            self.compiled_calls += 1
            for peer, sc in zip(sampled_peers, scores):
                passed[peer] = bool(sc <= hp.sync_score_threshold)
        for peer in fast_set:
            ok = passed.get(peer, False)
            ctx.fast_pass[peer] = ok
            self._last_fast_check[peer] = ctx.round_idx
            st = self._state(peer)
            if not ok:
                st.mu *= hp.fast_eval_penalty
            st.last_fast_pass = ok
        ctx.fast_set = fast_set
        return ctx

    # --------------------------------------------------- uniqueness audit
    def _put_block(self, peer: str, round_idx: int) -> int:
        """Server-side timestamp of the peer's round payload (tie-break
        for cluster arbitration when no replayer is available)."""
        bucket = self.store.buckets.get(peer)
        meta = bucket.head(self.store.gradient_key(round_idx)) \
            if bucket is not None else None
        return meta.put_block if meta is not None else 1 << 62

    def stage_uniqueness(self, ctx: RoundContext) -> RoundContext:
        """Proof-of-unique-work audit over S_t (``repro.audit``).

        Three checks, in escalating cost: (1) the chain commitment of the
        consumed batch must match the chain-derived assignment digest;
        (2) one jitted count-sketch + pairwise-cosine call over the
        stacked payloads flags copy clusters — within the round and
        against the previous round's sketches (delayed copies); (3)
        replay audits (the peers' own shared jitted local-step program)
        arbitrate clusters — the member matching its own replay is the
        original — and spot-check ``spot_k`` random peers, with the
        per-round replay-target count bounded by
        ``AuditConfig.replay_cap``. Flags zero the round score for
        ``ban_rounds`` rounds (scoreboard stage) and demote the OpenSkill
        rating.
        """
        ac = self.audit_cfg
        if not ac.enabled:
            return ctx
        self._select_eval_set(ctx)
        flagged: Dict[str, str] = {}
        audit: Dict[str, Any] = {}
        if ctx.eval_set:
            # (1) commit-then-reveal: the digest a peer committed must
            # match the batch the chain assigned it
            for p in ctx.eval_set:
                committed = self.chain.batch_commitment(p, ctx.round_idx)
                if committed is None:
                    if ac.require_commit:
                        flagged[p] = "missing_commit"
                    continue
                expected = assignment.batch_digest(
                    self._assigned_batch(ctx, p))
                if committed != expected:
                    flagged[p] = "commit_mismatch"
            # (2) fingerprints: ONE compiled call sketches the whole
            # (padded) eval stack and compares it against itself + the
            # recent-rounds reference window. The reference is padded to
            # AUDIT_REF_ROUNDS x the sticky peer bucket — its capacity,
            # not its occupancy — so the entry point never retraces as
            # the window fills or the eval set wobbles.
            k = len(ctx.eval_set)
            rows = _payload_rows(ctx.stacked_payloads)
            prev_uids = [u for uids, _ in self._prev_sketches for u in uids]
            ref = padding.pad_rows(
                [row for _, arr in self._prev_sketches for row in arr],
                ac.fingerprint_dim, bucket=AUDIT_REF_ROUNDS * rows)
            sk, cur, prev = self._obs_dispatch(
                "fingerprint", self._fingerprint, ctx.stacked_payloads,
                jnp.asarray(ref))
            self.compiled_calls += 1
            sk = np.asarray(sk)[:k]
            cur = np.asarray(cur)[:k, :k]
            prev = np.asarray(prev)[:k]
            thr = ac.similarity_threshold
            # a cross-round match makes a peer a delayed-copy SUSPECT;
            # the verdict goes through replay arbitration below (never
            # unconditional — pseudo-gradients can be temporally
            # correlated, and the honest victim must survive matching
            # its own past payload republished under a copycat's uid)
            delayed: List[str] = []
            for i, p in enumerate(ctx.eval_set):
                if p in flagged:
                    continue
                if any(q != p and prev[i, j] >= thr
                       for j, q in enumerate(prev_uids)):
                    delayed.append(p)
            clusters = fingerprint.similarity_clusters(cur, ctx.eval_set,
                                                       thr)
            audit["clusters"] = [list(c) for c in clusters]
            # (3) replay: arbitration of clusters + delayed suspects,
            # plus random spot checks
            spot: List[str] = []
            if self._replayer is not None and ac.spot_k > 0:
                pool = [p for p in ctx.eval_set if p not in flagged]
                take = min(ac.spot_k, len(pool))
                if take:
                    picks = self._audit_rng.choice(len(pool), size=take,
                                                   replace=False)
                    spot = [pool[i] for i in sorted(picks.tolist())]
            targets = sorted({p for c in clusters for p in c
                              if p not in flagged}
                             | set(spot) | set(delayed))
            # bound worst-case replay cost (AuditConfig.replay_cap): an
            # unusually large copy cluster must not grow the sticky
            # replay bucket (and retrace the batched replay program) or
            # stall the round on O(cluster) local steps. Spot checks and
            # delayed suspects always replay; cluster members are sampled
            # round-robin, each cluster's earliest upload first (the
            # strongest original-candidate heuristic) then randomly —
            # members skipped this round are NEVER flagged (no replay
            # evidence, and arbitration over a victim-less sample can
            # crown a lucky copy), so capping cannot create false
            # positives; their verdicts defer to later rounds' samples.
            capped_out: set = set()
            if (self._replayer is not None and ac.replay_cap > 0
                    and len(targets) > ac.replay_cap):
                must = [p for p in sorted(set(spot) | set(delayed))
                        if p not in flagged][:ac.replay_cap]
                chosen = set(must)
                pools = []
                for cluster in clusters:
                    pool = [p for p in cluster
                            if p not in flagged and p not in chosen]
                    self._audit_rng.shuffle(pool)
                    pool.sort(key=lambda p: self._put_block(
                        p, ctx.round_idx))
                    if pool:
                        pools.append(pool)
                while len(chosen) < ac.replay_cap and pools:
                    for pool in list(pools):
                        if len(chosen) >= ac.replay_cap:
                            break
                        chosen.add(pool.pop(0))
                        if not pool:
                            pools.remove(pool)
                capped_out = set(targets) - chosen
                audit["replay_capped"] = len(capped_out)
                targets = sorted(chosen)
            # replay margin per target: cos(payload, replay(assigned)) −
            # cos(payload, replay(decoy)). Self-normalizing — both terms
            # decay together as error feedback accumulates, but only the
            # peer that actually trained on its assignment keeps a gap.
            # All audited peers replay in TWO batched dispatches (one
            # per batch shape: assigned stack, decoy stack) instead of
            # O(k) sequential local steps (ROADMAP PR-3 follow-up).
            replay_margin: Dict[str, float] = {}
            if self._replayer is not None and targets:
                reps_a = self._obs_dispatch(
                    "replay_assigned", self._replayer.replay_batch,
                    self.params,
                    [self._assigned_batch(ctx, p) for p in targets])
                reps_d = self._obs_dispatch(
                    "replay_decoy", self._replayer.replay_batch,
                    self.params,
                    [self._unassigned_batch(ctx, p) for p in targets])
                self.compiled_calls += 2
                rsk_a = np.asarray(self._obs_dispatch(
                    "sketch", self._sketch, reps_a))
                rsk_d = np.asarray(self._obs_dispatch(
                    "sketch", self._sketch, reps_d))
                self.compiled_calls += 2
                for i, p in enumerate(targets):
                    row = sk[ctx.stacked_index[p]]
                    replay_margin[p] = (
                        fingerprint.cosine(row, rsk_a[i])
                        - fingerprint.cosine(row, rsk_d[i]))
            for p in delayed:
                # the suspect is a copy unless its payload matches a
                # replay of its own assignment (the honest victim does;
                # without a replayer the cross-round match must stand).
                # A suspect squeezed out by the replay cap has no
                # evidence either way — deferred, like capped cluster
                # members, never flagged on the sentinel margin
                if p in capped_out:
                    continue
                if replay_margin.get(p, -2.0) < ac.replay_margin:
                    flagged[p] = "delayed_copy"
            for cluster in clusters:
                members = [p for p in cluster if p not in flagged]
                if not members:
                    continue
                if replay_margin:
                    # the original is the member whose payload matches a
                    # replay of its OWN assignment; copies carry the
                    # victim's work and hold no margin of their own
                    best = max(members,
                               key=lambda p: replay_margin.get(p, -2.0))
                    keep = (replay_margin.get(best, -2.0)
                            >= ac.replay_margin)
                else:
                    # no replayer: earliest upload wins the tie. This is
                    # a heuristic (a copier of a delayed payload can land
                    # first) — validators that can train must pass
                    # grad_fn so replay arbitration decides instead.
                    best = min(members, key=lambda p: self._put_block(
                        p, ctx.round_idx))
                    keep = True
                for p in members:
                    if p == best and keep:
                        continue
                    if p in capped_out:
                        # replay-capped member: no evidence either way
                        # this round, verdict deferred to a later
                        # round's sample (never a blind flag)
                        continue
                    flagged[p] = "copy_cluster"
            for p in spot:
                if (p not in flagged
                        and replay_margin.get(p, 1.0)
                        < ac.replay_margin):
                    flagged[p] = "replay_mismatch"
            audit["replay_margins"] = {
                p: round(float(s), 6)
                for p, s in sorted(replay_margin.items())}
            # only unflagged peers' sketches enter the reference window:
            # a copycat's stored sketch IS its victim's payload, and must
            # not come back as "someone else's previous work" next round
            keep_rows = [i for i, p in enumerate(ctx.eval_set)
                         if p not in flagged]
            if keep_rows:
                self._prev_sketches = (self._prev_sketches + [
                    ([ctx.eval_set[i] for i in keep_rows],
                     sk[np.asarray(keep_rows)])])[-AUDIT_REF_ROUNDS:]
        # strikes: a fresh flag zeroes the peer for ban_rounds; a clean
        # evaluated round works one strike off
        for p in ctx.eval_set:
            if p in flagged:
                self.audit_strikes[p] = ac.ban_rounds
            elif self.audit_strikes.get(p, 0) > 0:
                self.audit_strikes[p] -= 1
        ctx.audit_flagged = flagged
        ctx.audit = audit
        return ctx

    def _select_eval_set(self, ctx: RoundContext) -> None:
        """Sample S_t and stack its payloads once per round — shared by
        whichever of uniqueness / primary-eval runs first."""
        if ctx.eval_selected:
            return
        ctx.eval_selected = True
        hp = self.hp
        candidates = [p for p in ctx.active_peers
                      if self.store.within_put_window(
                          p, ctx.round_idx, self.chain.blocks_per_round)]
        self.rng.shuffle(candidates)
        eval_set = [p for p in candidates[:hp.eval_set_size]
                    if self._fetch_payload(ctx, p) is not None]
        ctx.eval_set = eval_set
        if not eval_set:
            return
        # pad the peer axis to the sticky bucket (a multiple of
        # eval_chunk so the chunked primary eval divides evenly): every
        # jitted consumer of the stack sees one pinned shape under churn
        bucket = self._pad.get("peers", len(eval_set),
                               multiple=max(hp.eval_chunk, 1))
        ctx.stacked_payloads = self.scheme.pad_payloads(
            self.scheme.stack_payloads(
                [ctx.payloads[p] for p in eval_set]), bucket)
        ctx.stacked_index = {p: i for i, p in enumerate(eval_set)}

    def _assigned_batch(self, ctx: RoundContext, peer: str):
        """SelectData(peer, t), computed once per round per peer (shared
        by the commitment check, replay audits and primary eval)."""
        if peer not in ctx.assigned_batches:
            ctx.assigned_batches[peer] = self.data["assigned"](
                peer, ctx.round_idx)
        return ctx.assigned_batches[peer]

    def _unassigned_batch(self, ctx: RoundContext, peer: str):
        """UnassignedData(peer, t), cached like the assigned batch
        (shared by the replay decoy and primary eval)."""
        if peer not in ctx.unassigned_batches:
            ctx.unassigned_batches[peer] = self.data["unassigned"](
                peer, ctx.round_idx)
        return ctx.unassigned_batches[peer]

    def _resolve_baselines(self, ukeys: List[bytes], na: int, ua, ur):
        """Baseline losses for the round's unique batches, reusing the
        cross-validator cache per key: only the *missing* batches are
        evaluated, by gathering just the missed rows of the (padded)
        unique-batch stacks inside the compiled call (ROADMAP
        partial-reuse follow-up — all-or-nothing before). The returned
        per-stack baseline vectors are zero-padded to the stacks' bucket
        so the primary entry point's shapes stay pinned."""
        bucket = jax.tree.leaves(ua)[0].shape[0]
        vals = np.full(len(ukeys), np.nan, np.float64)
        if self.baseline_cache is not None:
            found = self.baseline_cache.lookup_partial(self.step, ukeys)
            for i, k in enumerate(ukeys):
                if k in found:
                    vals[i] = found[k]
        missing = [i for i in range(len(ukeys)) if np.isnan(vals[i])]
        if missing:
            ma = [i for i in missing if i < na]
            mr = [i - na for i in missing if i >= na]
            rows_a = padding.pad_index(np.asarray(ma, np.int32), bucket)
            rows_r = padding.pad_index(np.asarray(mr, np.int32), bucket)
            args = (self.params, ua, ur, jnp.asarray(rows_a),
                    jnp.asarray(rows_r))
            self._baseline_arg_spec = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                               jnp.asarray(x).dtype), args)
            got_a, got_r = self._obs_dispatch("baselines",
                                              self._baselines, *args)
            self.compiled_calls += 1
            self.baseline_calls += 1
            self.baseline_rows += len(missing)
            got = np.concatenate([np.asarray(got_a, np.float64)[:len(ma)],
                                  np.asarray(got_r, np.float64)[:len(mr)]])
            vals[missing] = got
            if (self.baseline_cache is not None
                    and self.chain.checkpoint_pointer == self.uid):
                self.baseline_cache.publish(
                    self.step, [ukeys[i] for i in missing], got)
        base_a = np.zeros(bucket, np.float32)
        base_a[:na] = vals[:na]
        base_r = np.zeros(bucket, np.float32)
        base_r[:len(ukeys) - na] = vals[na:]
        return jnp.asarray(base_a), jnp.asarray(base_r)

    def stage_primary_eval(self, ctx: RoundContext) -> RoundContext:
        """Batched LossScore over S_t — one compiled call per round."""
        hp = self.hp
        self._select_eval_set(ctx)
        eval_set = ctx.eval_set
        if not eval_set:
            return ctx
        beta = hp.eval_beta_frac * self.lr_at()
        batches_a = [self._assigned_batch(ctx, p) for p in eval_set]
        batches_r = [self._unassigned_batch(ctx, p) for p in eval_set]
        uniq_a, idx_a, keys_a = _unique_batches(batches_a)
        uniq_r, idx_r, keys_r = _unique_batches(batches_r)
        na, ukeys = len(uniq_a), keys_a + keys_r
        # pad the unique-batch stacks to one sticky bucket (rows repeat
        # batch 0 — valid inputs whose outputs are never gathered) and
        # the per-peer index/mask vectors to the peer bucket, so primary
        # + baselines hold one compiled shape as the dedup count wobbles
        # (a multiple of eval_chunk so the chunked baselines divide)
        bucket_u = self._pad.get("uniq", max(na, len(uniq_r)),
                                 multiple=max(hp.eval_chunk, 1))
        ua = padding.pad_axis0(_stack_batches(uniq_a), bucket_u, edge=True)
        ur = padding.pad_axis0(_stack_batches(uniq_r), bucket_u, edge=True)
        base_a, base_r = self._resolve_baselines(ukeys, na, ua, ur)
        n = len(eval_set)
        rows = _payload_rows(ctx.stacked_payloads)
        valid = np.zeros(rows, np.float32)
        valid[:n] = 1.0
        args = (self.params, ctx.stacked_payloads, ua, ur,
                jnp.asarray(padding.pad_index(idx_a, rows)),
                jnp.asarray(padding.pad_index(idx_r, rows)),
                base_a, base_r, jnp.float32(beta), jnp.asarray(valid))
        self._primary_arg_spec = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                           jnp.asarray(x).dtype), args)
        s_a, s_r = self._obs_dispatch("primary", self._primary, *args)
        self.compiled_calls += 1
        s_a, s_r = np.asarray(s_a)[:n], np.asarray(s_r)[:n]
        for i, p in enumerate(eval_set):
            ctx.loss_scores_assigned[p] = float(s_a[i])
            ctx.loss_scores_rand[p] = float(s_r[i])
            self._state(p).evals += 1
        return ctx

    def stage_scoreboard(self, ctx: RoundContext) -> RoundContext:
        """PoC μ (batched eq. 3) + OpenSkill + PEERSCORE + eq.-5 post.

        Audit verdicts land here: freshly flagged peers are demoted in
        the rating book, peers with active audit strikes are excluded
        from the OpenSkill match (a copied score must not steal rating
        from honest peers) and their round score is zeroed before the
        weights are posted on chain."""
        hp = self.hp
        banned = {p for p in ctx.active_peers
                  if self.audit_strikes.get(p, 0) > 0}
        banned |= set(ctx.audit_flagged)
        if ctx.eval_set:
            mu = np.array([self._state(p).mu for p in ctx.eval_set])
            s_a = np.array([ctx.loss_scores_assigned[p]
                            for p in ctx.eval_set])
            s_r = np.array([ctx.loss_scores_rand[p] for p in ctx.eval_set])
            new_mu = S.poc_update_batched(mu, s_a, s_r, hp.poc_gamma)
            for p, m in zip(ctx.eval_set, new_mu):
                self._state(p).mu = float(m)
        for p in sorted(ctx.audit_flagged):
            self.book.demote(p)
        # OpenSkill match over the random-subset scores
        match_scores = {p: s for p, s in ctx.loss_scores_rand.items()
                        if p not in banned}
        if len(match_scores) >= 2:
            self.book.match(match_scores)
        raw = {p: S.peer_score(
                   self._state(p).mu if hp.use_poc else 1.0,
                   self.book.ordinal(p))
               for p in ctx.active_peers}
        ctx.norm_scores = S.normalize_scores(raw, hp.norm_power)
        if banned:
            for p in banned:
                if p in ctx.norm_scores:
                    ctx.norm_scores[p] = 0.0
            total = sum(ctx.norm_scores.values())
            if total > 0:
                ctx.norm_scores = {p: v / total
                                   for p, v in ctx.norm_scores.items()}
        self.chain.post_weights(self.uid, ctx.norm_scores)
        ctx.weights = S.top_g_weights(ctx.norm_scores, hp.top_g)
        if banned:
            # a banned peer must never be topped up to 1/G by rank ties
            # (eq. 6 hands the worst peer a slot whenever |peers| <= G)
            for p in banned:
                if p in ctx.weights:
                    ctx.weights[p] = 0.0
            total = sum(ctx.weights.values())
            if total > 0:
                ctx.weights = {p: v / total for p, v in ctx.weights.items()}
        return ctx

    def stage_aggregate(self, ctx: RoundContext) -> RoundContext:
        """Top-G coordinated DeMo update (eq. 6) in one fused compiled
        call, reusing stacked eval payloads where possible."""
        ctx.lr = self.lr_at()
        contributors = eligible_contributors(ctx.weights, self.store,
                                             self.chain, ctx.round_idx)
        self.current_top_g = contributors
        ctx.contributors = contributors
        if not contributors:
            return ctx
        rows = [ctx.stacked_index.get(p) for p in contributors]
        if ctx.stacked_payloads is not None and None not in rows:
            stacked = ctx.stacked_payloads
        else:
            payloads = [pl for pl in (self._fetch_payload(ctx, p)
                                      for p in contributors)
                        if pl is not None]
            if not payloads:
                return ctx
            stacked = self.scheme.pad_payloads(
                self.scheme.stack_payloads(payloads),
                self._agg_pad.get("agg_stack", len(payloads)))
            rows = list(range(len(payloads)))
        # pad the contributor rows to the sticky bucket with zero-weight
        # row-0 gathers: exact no-op contributions, one compiled shape
        n = len(rows)
        bucket = self._agg_pad.get("agg", n)
        weights = np.zeros(bucket, np.float32)
        weights[:n] = 1.0 / n
        self.params = self._obs_dispatch(
            "aggregate", self._agg, self.params, stacked,
            jnp.asarray(padding.pad_index(np.asarray(rows, np.int32),
                                          bucket)),
            jnp.float32(ctx.lr), jnp.asarray(weights))
        self.compiled_calls += 1
        self.step += 1
        return ctx

    # ------------------------------------------------------------ round
    def build_context(self, round_idx: int, active_peers: List[str],
                      fast_set_size: Optional[int] = None) -> RoundContext:
        return RoundContext(round_idx=round_idx,
                            active_peers=list(active_peers),
                            fast_set_size=fast_set_size)

    def begin_round_obs(self, ctx: RoundContext) -> None:
        """Open the round: reset the stage clock and (with a recorder)
        the round span. Callers composing stages manually — the sim
        engine splits the pipeline at ``stage_aggregate`` — bracket
        their stage calls with this and :meth:`end_round_obs`."""
        self.last_stage_ms = {}
        if self.obs is not None:
            self._round_span = self.obs.tracer.begin(
                f"round-{ctx.round_idx}", cat="round", tid=self.uid,
                round=ctx.round_idx, peers=len(ctx.active_peers))

    def end_round_obs(self, ctx: RoundContext) -> None:
        """Close the round span and report the round's metric deltas."""
        if self.obs is None:
            return
        self.obs.tracer.end(self._round_span)
        self._round_span = None
        self.obs.observe_validator_round(self, ctx)

    def run_stage(self, stage: Callable[[RoundContext], RoundContext],
                  ctx: RoundContext) -> RoundContext:
        """Run one stage, timing it into ``last_stage_ms`` (and a stage
        span when a recorder is attached) — the single timing path for
        :meth:`run_stages` AND external stage composers."""
        name = getattr(stage, "__name__", repr(stage)).replace("stage_",
                                                               "")
        tracer = self.obs.tracer if self.obs is not None else None
        span = (tracer.begin(name, cat="stage", tid=self.uid)
                if tracer is not None else None)
        t0 = time.perf_counter()
        try:
            ctx = stage(ctx)
        finally:
            self.last_stage_ms[name] = (time.perf_counter() - t0) * 1e3
            if tracer is not None:
                tracer.end(span)
        return ctx

    def _obs_dispatch(self, name: str, fn: Callable, *args):
        """Wrap one jitted entry-point dispatch in a trace span (so a
        retrace's backend-compile seconds land on the exact call that
        caused it). Identical call, zero overhead when untraced."""
        if self.obs is None or not self.obs.tracer.enabled:
            return fn(*args)
        with self.obs.tracer.span(name, cat="dispatch", tid=self.uid):
            return fn(*args)

    def run_stages(self, ctx: RoundContext) -> RoundContext:
        self.begin_round_obs(ctx)
        try:
            for stage in self.stages:
                ctx = self.run_stage(stage, ctx)
        finally:
            self.end_round_obs(ctx)
        return ctx

    def run_round(self, round_idx: int, active_peers: List[str],
                  fast_set_size: Optional[int] = None) -> RoundReport:
        ctx = self.build_context(round_idx, active_peers, fast_set_size)
        return self.run_stages(ctx).report()
