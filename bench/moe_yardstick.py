"""The yardstick of a latent-attention and expert configuration (a
DeepSeek-V2 config file): its model FLOPs, the operations and bytes of
its grouped expert matmuls, and the split of device time over the
block's named scopes.

Model FLOPs follow ``yardstick.py``: 2 per multiply-add of every matrix
product the model requires, the causal attention products over the
S(S+1)/2 pairs, a backward pass twice its forward, no recomputation.
Latent attention counts its four projections (W_q, W_kv_a, W_kv_b,
W_o) and q.k over the nope and rope dims and p.v over the value dims;
an expert layer counts its router, its shared experts and its held
experts' work at its expectation: each token picks k of the E experts,
so k·n/E of its picks fall on the n held here.
"""
from __future__ import annotations

from typing import Dict, Optional

import scopecut

# the block's scope vocabulary (repro.obs.trace.BLOCK_SCOPES); the tests
# hold the two equal
BLOCK_SCOPES = ("mla", "moe.route", "moe.dispatch", "moe.experts",
                "moe.combine", "moe.shared")
MOE_SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine")
# products of one expert layer per micro-batch that the grouped matmuls
# execute: 3 forward, the same 3 again where the backward recomputes
# the layer (remat), and the data and weight gradients of each
GROUPED_PRODUCTS = 12


def _mla_params(c: dict) -> int:
    d, H = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    r = c["kv_lora_rank"]
    return (d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
            + H * dv * d)


def expert_params(c: dict) -> int:
    """Parameters of one routed expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def forward_flops(c: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one forward pass over ``batch`` rows of ``seq``
    tokens for config file ``c``."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    dense = c["first_k_dense_replace"]
    E, n, k = (c["router_outputs"], c["n_routed_experts"],
               c["num_experts_per_tok"])
    dense_layer = _mla_params(c) + 3 * d * c["intermediate_size"]
    moe_layer = (_mla_params(c) + d * E
                 + c["n_shared_experts"] * expert_params(c)
                 + k * n / E * expert_params(c))
    per_token = 2 * (dense * dense_layer + (L - dense) * moe_layer
                     + d * c["vocab_size"])
    H = c["num_attention_heads"]
    width = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    attn = 2 * H * width * (seq * (seq + 1) // 2) * L
    return float(batch * (seq * per_token + attn))


def train_step_flops(c: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: forward and backward."""
    return 3.0 * forward_flops(c, batch, seq)


def grouped_work(c: dict, rows: float, layer_batches: int,
                 dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and bytes that the grouped expert matmuls execute for
    ``rows`` held assignments (summed over the expert layers and the
    micro-batches of a step), ``layer_batches`` the number of (expert
    layer, micro-batch) pairs. Each product reads its expert weights and
    its rows in and writes its rows out: of a forward product rows x d
    in, rows x f out (the down product the other way round), and their
    gradients alike."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    weights = layer_batches * c["n_routed_experts"] * d * f
    return {"flops": GROUPED_PRODUCTS * 2.0 * rows * d * f,
            "bytes": GROUPED_PRODUCTS * dtype_bytes
            * (weights + rows * (d + f))}


def block_scope(op_name: Optional[str]) -> Optional[str]:
    """The innermost scope of ``BLOCK_SCOPES`` on an instruction's name
    stack, forward and backward alike; None outside every one."""
    if not op_name:
        return None
    found = None
    for part in op_name.split(";", 1)[0].split("/"):
        if part.startswith(scopecut._ROOTS):
            continue
        m = scopecut._UNWRAP.match(part)
        if m and m.group(1) in BLOCK_SCOPES:
            found = m.group(1)
    return found


def by_block(selfs: Dict[str, float],
             names: Dict[str, str]) -> Dict[str, float]:
    """Self time summed per scope of ``BLOCK_SCOPES`` (every scope
    present), through ``names`` (``scopecut.op_names``)."""
    out = dict.fromkeys(BLOCK_SCOPES, 0.0)
    for instr, secs in selfs.items():
        scope = block_scope(names.get(instr))
        if scope:
            out[scope] += secs
    return out
