"""Latent attention's two causal cores on the CPU: the splash kernel's
branch (``mla._attend_in_vmem``, the one a TPU compiles) in Pallas
interpret mode against the query-blocked XLA branch every other
platform runs, through the whole layer: output and every parameter's
gradient."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import reduced_config
from repro.models import layers, mla

B, S = 2, 256
# two blocks a side: the block above the diagonal is skipped, the two on
# it are masked, the one below is whole
BLOCKS = dataclasses.replace(
    mla.BLOCKS, block_q=128, block_kv=128, block_kv_compute=128,
    block_q_dkv=128, block_kv_dkv=128, block_kv_dkv_compute=128)


def _config(dtype):
    """DeepSeek-V2-Lite's heads at their published widths (nope 128,
    rope 64 with YaRN, v 128), 4 of them, on a small latent."""
    cfg = reduced_config("deepseek-v2-lite")
    return cfg.with_overrides(
        num_heads=4, num_kv_heads=4, dtype=dtype,
        mla=dataclasses.replace(cfg.mla, qk_nope_head_dim=128,
                                qk_rope_head_dim=64,
                                v_head_dim=128)).validate()


def _layer(core, cfg):
    def layer(p, x):
        out = core(*mla._project_heads(p, x, cfg))
        return layers.linear(p["wo"], out.reshape(B, S, -1))
    return layer


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 2e-2)])
def test_kernel_branch_matches_blocked(monkeypatch, dtype, tol):
    """The scale folded into q, the shared rope key broadcast to every
    head and the (B,H,S,D) transposes: the kernel's branch gives the
    blocked branch's output and gradients, to float32 round-off in
    float32 and to bfloat16's in bfloat16 (relative to the largest
    entry)."""
    monkeypatch.setattr(mla, "BLOCKS", BLOCKS)
    cfg = _config(dtype)
    assert cfg.rope_scaling is not None
    scale = mla.softmax_scale(cfg)
    kx, kp, kc = jax.random.split(jax.random.PRNGKey(5), 3)
    p = mla.init_mla(kp, cfg)
    x = jax.random.normal(kx, (B, S, cfg.d_model)).astype(dtype)
    cot = jax.random.normal(kc, (B, S, cfg.d_model)).astype(dtype)
    vmem = _layer(functools.partial(mla._attend_in_vmem, scale=scale,
                                    interpret=True), cfg)
    blocked = _layer(functools.partial(mla._attend_blocked, scale=scale,
                                       q_block=512), cfg)

    def run(layer, p, x, cot):
        out, pull = jax.vjp(layer, p, x)
        return jax.tree.map(lambda a: a.astype(jnp.float32),
                            (out, *pull(cot)))

    got, want = (jax.tree.map(np.asarray, jax.jit(run, static_argnums=0)(
        layer, p, x, cot)) for layer in (vmem, blocked))
    # the blocked branch is what the CPU runs
    np.testing.assert_allclose(
        want[0], np.asarray(jax.jit(mla.attend_full, static_argnums=2)(
            p, x, cfg), np.float32), atol=tol * np.max(np.abs(want[0])))
    for (path, w), g in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree.leaves(got)):
        peak = float(np.max(np.abs(w)))
        assert peak > 0
        np.testing.assert_allclose(g / peak, w / peak, atol=tol,
                                   err_msg=jax.tree_util.keystr(path))
