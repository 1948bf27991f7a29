"""Model builder: init / forward / loss / decode for all six families.

Public API (used by training, serving, launch, tests):

    params = init_params(cfg, key)
    loss, metrics = loss_fn(params, batch, cfg, num_groups=G)
    logits = forward(params, batch, cfg, num_groups=G)
    cache = init_cache(cfg, batch_size, seq_len, dtype)
    logits, cache = decode_step(params, tokens_1, cache, cfg)

``batch`` is a dict: tokens (B,S) int32, labels (B,S) int32, and for
stub-frontend families patch_embeds/frames (B,P,e) float.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import attention, layers, mla, moe, rwkv6, ssm
from repro.configs.base import ModelConfig
from repro.obs import trace

# --------------------------------------------------------------- helpers


def layer_window(cfg: ModelConfig, li: int) -> int:
    """Static per-layer attention window (0 = full causal)."""
    if cfg.attn_window <= 0:
        return 0
    if cfg.family == "hybrid":
        # hymba: a few global-attention layers (first / middle / last)
        if li in (0, cfg.num_layers // 2, cfg.num_layers - 1):
            return 0
    return cfg.attn_window


def is_moe_layer(cfg: ModelConfig, li: int) -> bool:
    return (cfg.moe is not None and cfg.moe.num_experts > 0
            and li >= cfg.moe.first_dense_layers)


# --------------------------------------------------------------- init


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    cfg.validate()
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, cfg.num_layers + 4)
    p: Dict[str, Any] = {
        "embed": layers.init_embedding(keys[-1], cfg.padded_vocab,
                                       cfg.d_model, dtype),
        "final_norm": layers.init_rmsnorm(cfg.d_model, dtype),
        "layers": [],
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = layers.init_linear(keys[-2], cfg.d_model,
                                          cfg.padded_vocab, dtype, scale=0.02)
    if cfg.frontend is not None and cfg.frontend.kind != "none":
        p["projector"] = layers.init_linear(keys[-3], cfg.frontend.embed_dim,
                                            cfg.d_model, dtype)
    for li in range(cfg.num_layers):
        p["layers"].append(_init_block(keys[li], cfg, li))
    return p


def _init_block(key, cfg: ModelConfig, li: int):
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    blk: Dict[str, Any] = {"norm1": layers.init_rmsnorm(cfg.d_model, dtype),
                           "norm2": layers.init_rmsnorm(cfg.d_model, dtype)}
    if cfg.family == "ssm":
        blk["time_mix"] = rwkv6.init_time_mix(ks[0], cfg)
        blk["channel_mix"] = rwkv6.init_channel_mix(ks[1], cfg)
        return blk
    # attention flavor
    if cfg.mla is not None:
        blk["attn"] = mla.init_mla(ks[0], cfg)
    else:
        blk["attn"] = attention.init_attn(ks[0], cfg)
    if cfg.family == "hybrid":
        blk["ssm"] = ssm.init_ssm(ks[1], cfg)
        blk["mix_norm_a"] = layers.init_rmsnorm(cfg.d_model, dtype)
        blk["mix_norm_s"] = layers.init_rmsnorm(cfg.d_model, dtype)
    if cfg.cross_attention:
        blk["cross"] = attention.init_cross_attn(ks[2], cfg)
        blk["norm_x"] = layers.init_rmsnorm(cfg.d_model, dtype)
    # ffn flavor
    if is_moe_layer(cfg, li):
        blk["moe"] = moe.init_moe(ks[3], cfg)
    else:
        blk["mlp"] = layers.init_swiglu(ks[3], cfg.d_model, cfg.d_ff, dtype)
    return blk


# --------------------------------------------------------------- forward


def _embed_inputs(params, batch, cfg: ModelConfig):
    """Returns (x (B,S,d), text_offset, enc_states or None)."""
    dtype = jnp.dtype(cfg.dtype)
    tok = layers.embed(params["embed"], batch["tokens"], dtype)
    enc = None
    offset = 0
    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        pe = layers.linear(params["projector"],
                           batch["patch_embeds"].astype(dtype))
        tok = jnp.concatenate([pe, tok], axis=1)
        offset = cfg.frontend.num_prefix_tokens
    elif cfg.frontend is not None and cfg.frontend.kind == "audio":
        enc = layers.linear(params["projector"],
                            batch["frames"].astype(dtype))
    return tok, offset, enc


def _block_seq(blk, x, cfg: ModelConfig, li: int, enc_kv, num_groups: int):
    """Full-sequence block application. Returns (x, aux_loss, counts):
    an expert layer's ``moe.moe_ffn`` counts, None for other layers."""
    aux, counts = jnp.float32(0.0), None
    if cfg.family == "ssm":
        tm, _ = rwkv6.time_mix(blk["time_mix"], layers.rmsnorm(blk["norm1"], x,
                                                               cfg.norm_eps),
                               cfg)
        x = x + tm
        x = x + rwkv6.channel_mix_seq(blk["channel_mix"],
                                      layers.rmsnorm(blk["norm2"], x,
                                                     cfg.norm_eps))
        return x, aux, counts

    h = layers.rmsnorm(blk["norm1"], x, cfg.norm_eps)
    w = layer_window(cfg, li)
    if cfg.mla is not None:
        attn_out = mla.attend_full(blk["attn"], h, cfg)
    else:
        attn_out = attention.attend_full(blk["attn"], h, cfg, layer_window=w)
    if cfg.family == "hybrid":
        ssm_out, _ = ssm.ssm_seq(blk["ssm"], h, cfg)
        attn_out = 0.5 * (layers.rmsnorm(blk["mix_norm_a"], attn_out,
                                         cfg.norm_eps)
                          + layers.rmsnorm(blk["mix_norm_s"], ssm_out,
                                           cfg.norm_eps))
    x = x + attn_out
    if cfg.cross_attention and enc_kv is not None:
        x = x + attention.attend_cross(blk["cross"],
                                       layers.rmsnorm(blk["norm_x"], x,
                                                      cfg.norm_eps),
                                       enc_kv, cfg)
    h2 = layers.rmsnorm(blk["norm2"], x, cfg.norm_eps)
    if "moe" in blk:
        ffn_out, aux, counts = moe.moe_ffn(blk["moe"], h2, cfg,
                                           num_groups=num_groups)
    else:
        ffn_out = layers.swiglu(blk["mlp"], h2)
    return x + ffn_out, aux, counts


def _moe_counts(per_layer):
    """The expert layers' counts, (layers, 2), or None without any."""
    per_layer = [jnp.atleast_2d(c) for c in per_layer if c is not None]
    return jnp.concatenate(per_layer) if per_layer else None


def _trunk(params, batch, cfg: ModelConfig, num_groups: int,
           remat: bool = False):
    x, offset, enc = _embed_inputs(params, batch, cfg)
    aux_total, counts = jnp.float32(0.0), []
    for li, blk in enumerate(params["layers"]):
        enc_kv = None
        if cfg.cross_attention and enc is not None:
            enc_kv = attention.cross_kv(blk["cross"], enc, cfg)
        fn = functools.partial(_block_seq, cfg=cfg, li=li, enc_kv=enc_kv,
                               num_groups=num_groups)
        if remat:
            fn = jax.checkpoint(fn)
        x, aux, c = fn(blk, x)
        aux_total = aux_total + aux
        counts.append(c)
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, offset, aux_total, _moe_counts(counts)


def _unembed(params, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return x @ params["embed"]["w"].astype(x.dtype).T
    return layers.linear(params["lm_head"], x)


# ------------------------------------------------------- scan-over-layers
#
# Production steps lower 24-60-layer models; unrolled layers make XLA
# compile time O(layers). Consecutive layers with the same static signature
# (window, moe-ness) are stacked along a leading dim and applied with
# lax.scan — the body is partitioned once. Numerics are identical to the
# unrolled path (tests assert it).


def layer_signature(cfg: ModelConfig, li: int):
    return (layer_window(cfg, li), is_moe_layer(cfg, li))


def layer_groups(cfg: ModelConfig):
    """Runs of consecutive same-signature layers: [(start, length), ...]."""
    runs = []
    for li in range(cfg.num_layers):
        sig = layer_signature(cfg, li)
        if runs and runs[-1][2] == sig:
            runs[-1][1] += 1
        else:
            runs.append([li, 1, sig])
    return [(s, n) for s, n, _ in runs]


def stack_params(params, cfg: ModelConfig):
    """Unrolled param tree -> grouped/stacked tree for the scan trunk."""
    out = {k: v for k, v in params.items() if k != "layers"}
    out["groups"] = []
    for s, n in layer_groups(cfg):
        blks = params["layers"][s:s + n]
        if n == 1:
            out["groups"].append(blks[0])
        else:
            out["groups"].append(
                jax.tree.map(lambda *ls: jnp.stack(ls), *blks))
    return out


def init_params_stacked(cfg: ModelConfig, key):
    return stack_params(init_params(cfg, key), cfg)


def _trunk_stacked(params, batch, cfg: ModelConfig, num_groups: int,
                   remat: bool = False):
    x, offset, enc = _embed_inputs(params, batch, cfg)
    aux_total, counts = jnp.float32(0.0), []
    for (start, n), blk in zip(layer_groups(cfg), params["groups"]):
        def apply_one(blk_l, x_in):
            enc_kv = None
            if cfg.cross_attention and enc is not None:
                enc_kv = attention.cross_kv(blk_l["cross"], enc, cfg)
            fn = functools.partial(_block_seq, cfg=cfg, li=start,
                                   enc_kv=enc_kv, num_groups=num_groups)
            if remat:
                fn = jax.checkpoint(fn)
            return fn(blk_l, x_in)

        if n == 1:
            x, aux, c = apply_one(blk, x)
            aux_total = aux_total + aux
            counts.append(c)
        else:
            def body(carry, blk_l):
                x_c, aux_c = carry
                x2, a, c = apply_one(blk_l, x_c)
                return (x2, aux_c + a), c

            (x, aux_total), c = jax.lax.scan(body, (x, aux_total), blk)
            counts.append(c)
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, offset, aux_total, _moe_counts(counts)


def forward(params, batch, cfg: ModelConfig, num_groups: int = 1,
            remat: bool = False, scan_layers: bool = False):
    trunk = _trunk_stacked if scan_layers else _trunk
    with jax.named_scope(trace.SCOPE_MODEL):
        x, offset, _, _ = trunk(params, batch, cfg, num_groups, remat)
        logits = _unembed(params, x, cfg)
        if offset:
            logits = logits[:, offset:]
        return logits


def loss_fn(params, batch, cfg: ModelConfig, num_groups: int = 1,
            remat: bool = False, ce_chunks: int = 0,
            scan_layers: bool = False):
    """Next-token LM loss. Returns (loss, metrics): ``ce``, ``aux`` and,
    with expert layers, ``moe_held`` and ``moe_max_load`` (per expert
    layer: the assignments that fell on held experts, and the most that
    fell on one of them)."""
    trunk = _trunk_stacked if scan_layers else _trunk
    with jax.named_scope(trace.SCOPE_MODEL):
        x, offset, aux, counts = trunk(params, batch, cfg, num_groups, remat)
        if offset:
            x = x[:, offset:]
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if ce_chunks > 1:
            emb_w = (params["embed"]["w"] if cfg.tie_embeddings
                     else params["lm_head"]["w"].T)
            ce = layers.chunked_cross_entropy(x, emb_w.astype(x.dtype),
                                              labels, mask, ce_chunks)
        else:
            logits = _unembed(params, x, cfg)
            ce = layers.cross_entropy(logits, labels, mask)
        metrics = {"ce": ce, "aux": aux}
        if counts is not None:
            metrics.update(moe_held=counts[:, 0], moe_max_load=counts[:, 1])
        return ce + aux, metrics


# --------------------------------------------------------------- decode


class DecodeCache(NamedTuple):
    """Per-layer cache stack + shared bits. Layers held as tuples."""
    layer_caches: tuple
    cross_kv: Optional[tuple]     # audio: per-layer (k, v) over frames


def init_cache(cfg: ModelConfig, batch_size: int, seq_len: int,
               dtype=None, frames: Optional[jnp.ndarray] = None,
               params=None) -> DecodeCache:
    dtype = dtype or jnp.dtype(cfg.dtype)
    caches = []
    for li in range(cfg.num_layers):
        if cfg.family == "ssm":
            caches.append(rwkv6.init_rwkv_state(cfg, batch_size, dtype))
            continue
        w = layer_window(cfg, li)
        if cfg.mla is not None:
            c = mla.init_mla_cache(cfg, batch_size, seq_len, dtype)
        else:
            c = attention.init_kv_cache(cfg, batch_size, seq_len, w, dtype)
        if cfg.family == "hybrid":
            c = (c, ssm.init_ssm_state(cfg, batch_size, dtype))
        caches.append(c)
    cross = None
    if cfg.cross_attention:
        if frames is not None and params is not None:
            enc = layers.linear(params["projector"], frames.astype(dtype))
            cross = tuple(attention.cross_kv(blk["cross"], enc, cfg)
                          for blk in params["layers"])
        else:
            F = cfg.frontend.num_prefix_tokens
            H, hd = cfg.num_heads, cfg.resolved_head_dim
            z = jnp.zeros((batch_size, F, H, hd), dtype)
            cross = tuple((z, z) for _ in range(cfg.num_layers))
    return DecodeCache(layer_caches=tuple(caches), cross_kv=cross)


def _block_decode(blk, x, c, cfg: ModelConfig, li: int, cross_kv_li,
                  seq_len: int, num_groups: int):
    """One layer of single-token decode. Returns (x, new layer cache)."""
    if cfg.family == "ssm":
        h = layers.rmsnorm(blk["norm1"], x, cfg.norm_eps)
        tm, c2 = rwkv6.time_mix_step(blk["time_mix"], h, c, cfg)
        x = x + tm
        h2 = layers.rmsnorm(blk["norm2"], x, cfg.norm_eps)
        x = x + rwkv6.channel_mix(blk["channel_mix"], h2,
                                  c.shift_cm[:, None])
        return x, c2._replace(shift_cm=h2[:, 0])
    h = layers.rmsnorm(blk["norm1"], x, cfg.norm_eps)
    w = layer_window(cfg, li)
    if cfg.family == "hybrid":
        kv_c, ssm_c = c
    else:
        kv_c, ssm_c = c, None
    if cfg.mla is not None:
        attn_out, kv_c = mla.attend_decode(blk["attn"], h, kv_c, cfg)
    else:
        ring = attention.is_ring(w, seq_len or kv_c.k.shape[1])
        attn_out, kv_c = attention.attend_decode(blk["attn"], h, kv_c, cfg,
                                                 layer_window=w, ring=ring)
    if cfg.family == "hybrid":
        ssm_out, ssm_c = ssm.ssm_step(blk["ssm"], h, ssm_c, cfg)
        attn_out = 0.5 * (layers.rmsnorm(blk["mix_norm_a"], attn_out,
                                         cfg.norm_eps)
                          + layers.rmsnorm(blk["mix_norm_s"], ssm_out,
                                           cfg.norm_eps))
        new_c = (kv_c, ssm_c)
    else:
        new_c = kv_c
    x = x + attn_out
    if cfg.cross_attention and cross_kv_li is not None:
        x = x + attention.attend_cross(blk["cross"],
                                       layers.rmsnorm(blk["norm_x"], x,
                                                      cfg.norm_eps),
                                       cross_kv_li, cfg)
    h2 = layers.rmsnorm(blk["norm2"], x, cfg.norm_eps)
    if "moe" in blk:
        ffn_out, _, _ = moe.moe_ffn(blk["moe"], h2, cfg, num_groups=num_groups)
    else:
        ffn_out = layers.swiglu(blk["mlp"], h2)
    return x + ffn_out, new_c


def decode_step(params, tokens, cache: DecodeCache, cfg: ModelConfig,
                seq_len: int = 0, num_groups: int = 1):
    """One decode step. tokens: (B,1) -> (logits (B,1,V), new cache).

    ``seq_len`` is the static nominal context length (decides ring-ness).
    """
    dtype = jnp.dtype(cfg.dtype)
    x = layers.embed(params["embed"], tokens, dtype)
    new_caches = []
    for li, blk in enumerate(params["layers"]):
        cross = cache.cross_kv[li] if cache.cross_kv is not None else None
        x, c2 = _block_decode(blk, x, cache.layer_caches[li], cfg, li,
                              cross, seq_len, num_groups)
        new_caches.append(c2)
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _unembed(params, x, cfg)
    return logits, DecodeCache(layer_caches=tuple(new_caches),
                               cross_kv=cache.cross_kv)


def group_cache(cache: DecodeCache, cfg: ModelConfig) -> DecodeCache:
    """Stack per-layer caches to match ``stack_params`` grouping."""
    groups = []
    for s, n in layer_groups(cfg):
        cs = cache.layer_caches[s:s + n]
        groups.append(cs[0] if n == 1
                      else jax.tree.map(lambda *ls: jnp.stack(ls), *cs))
    cross = None
    if cache.cross_kv is not None:
        cross = []
        for s, n in layer_groups(cfg):
            ck = cache.cross_kv[s:s + n]
            cross.append(ck[0] if n == 1
                         else jax.tree.map(lambda *ls: jnp.stack(ls), *ck))
        cross = tuple(cross)
    return DecodeCache(layer_caches=tuple(groups), cross_kv=cross)


def decode_step_stacked(params, tokens, cache: DecodeCache,
                        cfg: ModelConfig, seq_len: int = 0,
                        num_groups: int = 1):
    """Scan-over-layers decode on grouped params/caches (compile-time
    friendly for 60-layer models; numerics identical to decode_step)."""
    dtype = jnp.dtype(cfg.dtype)
    x = layers.embed(params["embed"], tokens, dtype)
    new_groups = []
    for gi, ((start, n), blk) in enumerate(zip(layer_groups(cfg),
                                               params["groups"])):
        c = cache.layer_caches[gi]
        cross = cache.cross_kv[gi] if cache.cross_kv is not None else None
        if n == 1:
            x, c2 = _block_decode(blk, x, c, cfg, start, cross, seq_len,
                                  num_groups)
        else:
            def body(x_c, inp):
                blk_l, c_l, cross_l = inp
                return _block_decode(blk_l, x_c, c_l, cfg, start, cross_l,
                                     seq_len, num_groups)

            xs = ((blk, c, cross) if cross is not None
                  else (blk, c, None))
            if cross is None:
                def body2(x_c, inp):
                    blk_l, c_l = inp
                    return _block_decode(blk_l, x_c, c_l, cfg, start, None,
                                         seq_len, num_groups)
                x, c2 = jax.lax.scan(body2, x, (blk, c))
            else:
                x, c2 = jax.lax.scan(body, x, (blk, c, cross))
        new_groups.append(c2)
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _unembed(params, x, cfg)
    return logits, DecodeCache(layer_caches=tuple(new_groups),
                               cross_kv=cache.cross_kv)


def build_model(cfg: ModelConfig):
    """Convenience bundle of bound functions."""
    return {
        "init": functools.partial(init_params, cfg),
        "loss": functools.partial(loss_fn, cfg=cfg),
        "forward": functools.partial(forward, cfg=cfg),
        "init_cache": functools.partial(init_cache, cfg),
        "decode_step": functools.partial(decode_step, cfg=cfg),
    }
