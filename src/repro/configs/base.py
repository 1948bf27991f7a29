"""Config system for the Gauntlet reproduction.

A single ``ModelConfig`` dataclass covers all six architecture families
(dense, moe, ssm, hybrid, vlm, audio). Family-specific knobs default to
``None``/0 and are validated per family. All configs are frozen dataclasses,
hashable so they can key jit caches.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

FAMILIES = ("dense", "moe", "ssm", "hybrid", "vlm", "audio")


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts sub-config (DeepSeekMoE-style fine-grained).

    The router scores all ``num_experts``; a chip holds the
    ``experts_held`` of them that start at ``expert_offset`` (expert
    parallelism) and computes their part of the layer's result."""

    num_experts: int = 0          # routed experts (the router's outputs)
    num_shared_experts: int = 0   # always-on shared experts
    top_k: int = 0                # routed experts per token
    expert_d_ff: int = 0          # per-expert FFN width
    router_aux_coef: float = 0.001  # sequence-wise balance loss alpha
    first_dense_layers: int = 1   # DeepSeek keeps layer 0 dense
    experts_held: int = 0         # experts held here; 0 = all of them
    expert_offset: int = 0        # the first expert held here

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (arXiv:2309.00071) with DeepSeek-V2's keys:
    ``factor`` stretches the ``original_max_position`` positions the
    model was pre-trained on; ``beta_fast`` and ``beta_slow`` bound the
    band of frequencies interpolated; the attention scale takes
    ``mscale_all_dim``'s factor squared (``repro.models.layers``)."""

    factor: float = 1.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2)."""

    kv_lora_rank: int = 0        # compressed KV latent dim
    q_lora_rank: int = 0         # 0 = full-rank Q
    qk_rope_head_dim: int = 64   # decoupled RoPE key/query dim
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128


@dataclass(frozen=True)
class SSMConfig:
    """State-space / RWKV sub-config."""

    state_size: int = 16          # per-head recurrent state (mamba d_state)
    head_dim: int = 64            # rwkv6 head size
    conv_kernel: int = 4          # mamba local conv width
    expand: int = 2               # mamba inner expansion
    chunk_len: int = 128          # chunked-scan length for training
    # intra-chunk matmul dtype for the chunked-WKV (perf knob: the decay
    # tensor is the memory hot-spot; bf16 halves its traffic, accumulation
    # stays fp32 via preferred_element_type)
    intra_dtype: str = "float32"


@dataclass(frozen=True)
class FrontendConfig:
    """Stubbed modality frontend: supplies precomputed embeddings.

    ``num_prefix_tokens`` embeddings of dim ``embed_dim`` are prepended
    (VLM patch tokens) or cross-attended (audio encoder frames).
    """

    kind: str = "none"            # none | vision | audio
    num_prefix_tokens: int = 0    # patch tokens (vlm) / encoder frames (audio)
    embed_dim: int = 0            # raw embedding dim before projector


@dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny"
    family: str = "dense"
    source: str = ""              # citation for the config
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0             # 0 => d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 4096
    max_seq_len: int = 8192
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnConfig] = None   # None = plain rope
    norm_eps: float = 1e-5
    qkv_bias: bool = False            # qwen2-style
    tie_embeddings: bool = False
    attn_window: int = 0              # 0 = full causal; >0 = sliding window
    swa_every: int = 1                # apply window to every n-th layer (danube/hymba mix)
    dtype: str = "bfloat16"           # activations/weights compute dtype
    param_dtype: str = "float32"      # master params
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    frontend: Optional[FrontendConfig] = None
    # hybrid (hymba): fraction of heads that are mamba vs attention
    hybrid_attn: bool = False
    # enc-dec (whisper): decoder cross-attends to frontend frames
    cross_attention: bool = False
    # distribution policy
    peer_axes: Tuple[str, ...] = ("data",)   # mesh axes that index peers
    long_context_ok: bool = False            # native sub-quadratic support

    # ---- derived -----------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def padded_vocab(self) -> int:
        """Megatron-style: embedding/lm-head rows padded to a multiple of
        256 so the vocab dim shards evenly; ``vocab_size`` stays authentic
        (tokens/labels never reference padded rows)."""
        return -(-self.vocab_size // 256) * 256

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    def validate(self) -> "ModelConfig":
        assert self.family in FAMILIES, self.family
        assert self.d_model > 0 and self.num_layers > 0
        if not self.attention_free:
            assert self.num_heads > 0
            assert self.num_heads % max(self.num_kv_heads, 1) == 0, (
                f"{self.name}: heads {self.num_heads} not multiple of kv "
                f"{self.num_kv_heads}")
        if self.family in ("moe",):
            assert self.moe is not None and self.moe.num_experts > 0
            m = self.moe
            assert 0 < m.held and m.expert_offset + m.held <= m.num_experts, (
                f"{self.name}: holds experts [{m.expert_offset}, "
                f"{m.expert_offset + m.held}) of {m.num_experts}")
        if self.family in ("ssm", "hybrid"):
            assert self.ssm is not None
        if self.family in ("vlm", "audio"):
            assert self.frontend is not None and self.frontend.kind != "none"
        return self

    def param_count(self) -> int:
        """Analytic parameter count (used for roofline MODEL_FLOPS)."""
        d, L, V = self.d_model, self.num_layers, self.vocab_size
        hd = self.resolved_head_dim
        emb = V * d * (1 if self.tie_embeddings else 2)
        per_layer = 0
        if self.family == "ssm":  # rwkv6: time-mix + channel-mix
            # r,k,v,g,w projections + output  (~6 d^2) + lora decays (small)
            per_layer = 6 * d * d + 2 * d * self.d_ff + d * self.d_ff
        else:
            if self.mla is not None:
                m = self.mla
                q_in = m.q_lora_rank or d
                per_layer += (d * m.q_lora_rank if m.q_lora_rank else 0)
                per_layer += q_in * self.num_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim)
                per_layer += d * (m.kv_lora_rank + m.qk_rope_head_dim)
                per_layer += m.kv_lora_rank * self.num_heads * (m.qk_nope_head_dim + m.v_head_dim)
                per_layer += self.num_heads * m.v_head_dim * d
            else:
                q = d * self.num_heads * hd
                kv = 2 * d * self.num_kv_heads * hd
                o = self.num_heads * hd * d
                per_layer += q + kv + o
            if self.family == "hybrid" and self.ssm is not None:
                di = self.ssm.expand * d
                per_layer += d * 2 * di + di * d + di * (2 * self.ssm.state_size + 1)
            if self.moe is not None and self.moe.num_experts:
                m = self.moe
                dense_ffn = 3 * d * self.d_ff
                expert_ffn = 3 * d * m.expert_d_ff
                moe_layers = L - m.first_dense_layers
                per_layer_moe = (m.held + m.num_shared_experts) * expert_ffn + d * m.num_experts
                # average: dense layers use dense ffn
                total_ffn = (m.first_dense_layers * dense_ffn + moe_layers * per_layer_moe) / L
                per_layer += int(total_ffn)
            else:
                per_layer += 3 * d * self.d_ff  # gate/up/down
        return int(emb + L * per_layer)

    def active_param_count(self) -> int:
        """Active params per token (MoE: shared + the top_k routed, of
        which a chip holding a share of the experts sees its share)."""
        if self.moe is None or not self.moe.num_experts:
            return self.param_count()
        m = self.moe
        d, L = self.d_model, self.num_layers
        full = self.param_count()
        expert_ffn = 3 * d * m.expert_d_ff
        moe_layers = L - m.first_dense_layers
        inactive = (moe_layers * m.held * (1 - m.top_k / m.num_experts)
                    * expert_ffn)
        return int(full - inactive)

    def with_overrides(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class AuditConfig:
    """Structured view over the proof-of-unique-work audit knobs.

    Assembled by :attr:`TrainConfig.audit` from the flat ``audit_*``
    fields and threaded through the validator's uniqueness stage, the
    replay auditor and the sim — one object instead of eight loose
    attributes.
    """

    enabled: bool = True
    fingerprint_dim: int = 256
    similarity_threshold: float = 0.9
    replay_margin: float = 0.02
    spot_k: int = 2
    ban_rounds: int = 3
    require_commit: bool = False
    # worst-case replay cost bound: at most this many replay targets per
    # round (0 = uncapped); oversized copy clusters are sampled instead
    # of replayed wholesale, so one giant cluster cannot grow the sticky
    # replay bucket (and retrace the batched replay program)
    replay_cap: int = 16
    # block whose chain hash seeds the per-run count-sketch; -1 resolves
    # to the first block after genesis registration closes (one round in)
    # so sketch collisions cannot be crafted offline before the run
    sketch_seed_block: int = -1

    def resolved_seed_block(self, blocks_per_round: int) -> int:
        return (self.sketch_seed_block if self.sketch_seed_block >= 0
                else blocks_per_round)


@dataclass(frozen=True)
class TrainConfig:
    """Gauntlet + scheme hyperparameters (paper §2-§3 defaults)."""

    seed: int = 0
    learning_rate: float = 4e-4
    warmup_steps: int = 250
    total_steps: int = 20000
    weight_decay: float = 0.1
    grad_clip: float = 0.0              # DeMo path relies on sign, not clip
    # gradient scheme (repro.schemes registry): what a payload IS, how a
    # local step produces it, and how aggregation applies it
    scheme: str = "demo"
    # DeMo (scheme="demo")
    demo_beta: float = 0.999            # error-feedback decay (momentum)
    demo_chunk: int = 64                # DCT chunk side s
    demo_topk: int = 32                 # coefficients kept per chunk
    # random-k sparsification (scheme="randk")
    randk_beta: float = 0.9             # error-feedback decay
    randk_frac: float = 0.02            # fraction of each tensor shipped
    # Gauntlet
    eval_beta_frac: float = 0.5         # c in beta_t = c * alpha_t  (c < 1)
    poc_gamma: float = 0.9              # EMA for mu_p (eq. 3)
    fast_eval_penalty: float = 0.75     # phi
    sync_score_threshold: float = 3.0
    norm_power: float = 2.0             # c in eq. 5
    top_g: int = 15                     # aggregation set size
    eval_set_size: int = 5              # |S_t| primary evals per round
    use_poc: bool = True                # ablation: drop eq.-3 mu from eq.-4
    openskill_mu: float = 25.0
    openskill_sigma: float = 25.0 / 3.0
    openskill_beta: float = 25.0 / 6.0
    openskill_kappa: float = 1e-4
    put_window: float = 60.0            # seconds (bucket-time units)
    tokens_per_peer: int = 400_000      # baseline script target
    # static-shape / bounded-memory eval (core.gauntlet, core.padding):
    # peer-count axes are padded to sticky power-of-two buckets so every
    # jitted round entry point compiles once per run, and the primary
    # eval optionally runs lax.map over vmap blocks of eval_chunk peers
    # so peak live memory is O(eval_chunk x params), not O(|S_t| x params)
    eval_chunk: int = 0                 # peers per fused block (0 = full vmap)
    eval_pad_min: int = 4               # smallest padding bucket
    eval_pad_cap: int = 0               # stop pow2 bucket growth here (0 = off)
    fast_prefetch_workers: int = 4      # fast-filter bucket-read threads (0 = off)
    # proof-of-unique-work audit (repro.audit, Validator.stage_uniqueness)
    audit_enabled: bool = True          # run the uniqueness stage
    audit_fingerprint_dim: int = 256    # count-sketch width
    audit_similarity_threshold: float = 0.9   # pairwise cosine => cluster
    # replay verdicts are self-normalizing: cos(payload, replay(assigned))
    # minus cos(payload, replay(decoy)) must clear this margin — honest
    # peers hold a wide positive gap even as error feedback accumulates
    audit_replay_margin: float = 0.02
    audit_spot_k: int = 2               # random replay audits per round
    audit_ban_rounds: int = 3           # rounds a flagged peer stays zeroed
    audit_require_commit: bool = False  # flag peers with NO commitment too
    audit_replay_cap: int = 16          # replay targets per round (0 = off)
    audit_sketch_seed_block: int = -1   # sketch-seed block (-1 = auto)

    @property
    def audit(self) -> AuditConfig:
        """The audit knobs as one structured object (see AuditConfig)."""
        return AuditConfig(
            enabled=self.audit_enabled,
            fingerprint_dim=self.audit_fingerprint_dim,
            similarity_threshold=self.audit_similarity_threshold,
            replay_margin=self.audit_replay_margin,
            spot_k=self.audit_spot_k,
            ban_rounds=self.audit_ban_rounds,
            require_commit=self.audit_require_commit,
            replay_cap=self.audit_replay_cap,
            sketch_seed_block=self.audit_sketch_seed_block)


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"
