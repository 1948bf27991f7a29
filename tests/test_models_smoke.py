"""Per-architecture smoke tests (deliverable f): every assigned arch as a
REDUCED variant — one train step and one decode step on CPU, asserting
output shapes and absence of NaNs. Family-defining structure is preserved
(GQA ratio, MoE routing, MLA, SSM heads, stub frontends, cross-attn)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ASSIGNED_ARCHS, reduced_config
from repro.data.pipeline import synthetic_batch
from repro.models import model as M

B, S = 2, 64


def _batch(cfg, key):
    return synthetic_batch(key, cfg.vocab_size, B, S, cfg)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_train_step(arch):
    cfg = reduced_config(arch)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    batch = _batch(cfg, key)

    def loss(p):
        return M.loss_fn(p, batch, cfg)[0]

    l0, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert jnp.isfinite(l0), arch
    gnorm = sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
                for g in jax.tree.leaves(grads)) ** 0.5
    assert gnorm > 0 and jnp.isfinite(gnorm), arch
    # one SGD step reduces loss on the same batch
    p2 = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
    l1 = jax.jit(loss)(p2)
    assert jnp.isfinite(l1)
    assert float(l1) < float(l0) + 1e-3, (arch, float(l0), float(l1))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_forward_shapes(arch):
    cfg = reduced_config(arch)
    key = jax.random.PRNGKey(1)
    params = M.init_params(cfg, key)
    batch = _batch(cfg, key)
    logits = jax.jit(lambda p, b: M.forward(p, b, cfg))(params, batch)
    assert logits.shape == (B, S, cfg.padded_vocab), arch
    assert bool(jnp.isfinite(logits).all()), arch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_decode_step(arch):
    cfg = reduced_config(arch)
    key = jax.random.PRNGKey(2)
    params = M.init_params(cfg, key)
    batch = _batch(cfg, key)
    cache = M.init_cache(cfg, B, 32, frames=batch.get("frames"),
                         params=params)
    logits, cache2 = jax.jit(
        lambda p, t, c: M.decode_step(p, t, c, cfg, seq_len=32))(
        params, batch["tokens"][:, :1], cache)
    assert logits.shape == (B, 1, cfg.padded_vocab), arch
    assert bool(jnp.isfinite(logits).all()), arch
    # cache positions advanced
    flat = jax.tree.leaves(cache2)
    assert any(x.dtype == jnp.int32 and x.ndim == 0 and int(x) == 1
               for x in flat), arch


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "rwkv6-3b", "hymba-1.5b",
                                  "whisper-base", "h2o-danube-3-4b",
                                  "internvl2-2b", "yi-6b"])
def test_decode_matches_full_forward(arch):
    """Teacher-forced decode == full forward (non-MoE archs: exact)."""
    cfg = reduced_config(arch)
    key = jax.random.PRNGKey(3)
    params = M.init_params(cfg, key)
    S_ = 16
    toks = jax.random.randint(key, (B, S_), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    if cfg.family == "vlm":
        batch["patch_embeds"] = 0.02 * jax.random.normal(
            key, (B, cfg.frontend.num_prefix_tokens, cfg.frontend.embed_dim))
    if cfg.family == "audio":
        batch["frames"] = 0.02 * jax.random.normal(
            key, (B, cfg.frontend.num_prefix_tokens, cfg.frontend.embed_dim))
    full = M.forward(params, batch, cfg)
    if cfg.family == "vlm":
        # decode path has no image prefix; compare text-only decode
        pytest.skip("vlm decode compares against prefix-prefilled cache")
    cache = M.init_cache(cfg, B, S_, frames=batch.get("frames"),
                         params=params)
    step = jax.jit(lambda p, t, c: M.decode_step(p, t, c, cfg, seq_len=S_))
    outs = []
    for t in range(S_):
        lg, cache = step(params, toks[:, t:t + 1], cache)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    err = float(jnp.max(jnp.abs(dec - full)))
    scale = float(jnp.max(jnp.abs(full))) + 1e-6
    assert err / scale < 5e-4, (arch, err, scale)


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "deepseek-v2-236b",
                                  "deepseek-v2-lite"])
def test_decode_matches_full_forward_moe(arch):
    """The dropless expert layer drops nothing, so per-token decode and
    the batched full forward agree (latent attention and YaRN in the
    DeepSeek-V2 entries)."""
    cfg = reduced_config(arch)
    key = jax.random.PRNGKey(4)
    params = M.init_params(cfg, key)
    S_ = 12
    toks = jax.random.randint(key, (B, S_), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    full = M.forward(params, batch, cfg)
    cache = M.init_cache(cfg, B, S_)
    step = jax.jit(lambda p, t, c: M.decode_step(p, t, c, cfg, seq_len=S_))
    outs = []
    for t in range(S_):
        lg, cache = step(params, toks[:, t:t + 1], cache)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    err = float(jnp.max(jnp.abs(dec - full)))
    scale = float(jnp.max(jnp.abs(full))) + 1e-6
    assert err / scale < 5e-4, (arch, err, scale)


def test_sliding_window_restricts_attention():
    """SWA variant: token far outside the window cannot influence logits."""
    cfg = reduced_config("h2o-danube-3-4b")  # attn_window=64
    key = jax.random.PRNGKey(5)
    params = M.init_params(cfg, key)
    S_ = 192
    toks = jax.random.randint(key, (1, S_), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    l1 = M.forward(params, batch, cfg)
    toks2 = toks.at[0, 0].set((toks[0, 0] + 1) % cfg.vocab_size)
    l2 = M.forward(params, {"tokens": toks2, "labels": toks2}, cfg)
    # last position is > window away from position 0 in every layer
    # (2 layers x window 64 = receptive field 128 < 191)
    delta_last = float(jnp.max(jnp.abs(l1[0, -1] - l2[0, -1])))
    delta_first = float(jnp.max(jnp.abs(l1[0, 0] - l2[0, 0])))
    assert delta_first > 1e-4          # sanity: the edit did something
    assert delta_last < 1e-5, delta_last
