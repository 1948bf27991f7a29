"""The benchmark's yardstick: published peaks per chip, and the model
operations a step requires, counted from a configuration's shapes.

Model FLOPs count the multiply-adds of every matrix product the model
requires (2 FLOPs each): the q/k/v/o and MLP projections, the causal
attention products, and the logits. The input embedding is a lookup and
counts nothing; a tied output head counts as the product it is. Causal
attention counts the S(S+1)/2 query-key pairs it needs, not the full
square. A backward pass counts twice its forward. Recomputation
(rematerialisation), padding rows and the DeMo codec's arithmetic are
not model work and are not counted.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float               # FLOP/s
    hbm_bw: float                   # bytes/s
    source: str


# keyed by ``jax.Device.device_kind``
PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def peaks(device_kind: str) -> Peaks:
    """Peaks of one chip; an unknown chip is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for {device_kind!r}; known: "
                         f"{sorted(PEAKS)}") from None


def forward_flops(c: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one forward pass over ``batch`` rows of ``seq``
    tokens for config file ``c``."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd, ff, vocab = c["head_dim"], c["intermediate_size"], c["vocab_size"]
    proj = d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * ff
    per_token = 2 * (L * proj + d * vocab)
    # q.k and p.v over the causal pairs of each sequence, every layer
    attn = 2 * 2 * H * hd * (seq * (seq + 1) // 2) * L
    return float(batch * (seq * per_token + attn))


def train_step_flops(c: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: forward and backward."""
    return 3.0 * forward_flops(c, batch, seq)

