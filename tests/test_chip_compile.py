"""Compile for one TPU v5e chip that is described, not attached: the
Pallas kernels at real widths and phase B's peer local step of
``chip_smoke.py``. Nothing runs; the TPU compiler refuses what the chip
would refuse (unaligned tiles, too much fast memory, too much HBM).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

GIB = 2 ** 30
# a templar-1b MLP leaf (2048, 8192) cut into 64 x 64 DeMo chunks
CHUNKS, S, K = (2048 // 64) * (8192 // 64), 64, 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read
    # back without the chip; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["dct2_chunks", "idct2_chunks"])
def test_dct_kernels_compile(one_chip, name):
    x = _sds((CHUNKS, S, S), jnp.float32, one_chip)
    _assert_kernel(getattr(ops, name).lower(x, interpret=False).compile())


def test_topk_kernel_compiles(one_chip):
    x = _sds((CHUNKS, S * S), jnp.float32, one_chip)
    _assert_kernel(ops.topk_chunks.lower(x, K, interpret=False).compile())


def test_ef_update_kernel_compiles(one_chip):
    leaf = _sds((2048, 8192), jnp.float32, one_chip)
    _assert_kernel(
        ops.ef_update.lower(leaf, leaf, 0.999, interpret=False).compile())


def test_wkv_kernel_compiles(one_chip):
    # rwkv6-3b: 40 heads of width 64, chunk 64, one 4096-token sequence
    from repro.configs.registry import get_config
    cfg = get_config("rwkv6-3b")
    n = cfg.ssm.head_dim
    strip = _sds((cfg.num_heads, 4096, n), jnp.float32, one_chip)
    u = _sds((n,), jnp.float32, one_chip)
    _assert_kernel(ops.wkv_chunks.lower(
        strip, strip, strip, strip, u, interpret=False,
        chunk=cfg.ssm.chunk_len).compile())


def test_phase_b_local_step_fits_one_chip(one_chip):
    sys.path.insert(0, ROOT)
    import chip_smoke
    from repro.models import model as M
    from repro.schemes import make_scheme
    from repro.training.peer import shared_local_step

    cfg = chip_smoke.templar(chip_smoke.PHASE_B_LAYERS)
    hp = chip_smoke.phase_b_hp()

    def on_chip(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                            tree)

    params = on_chip(jax.eval_shape(lambda: M.init_params(
        cfg, jax.random.PRNGKey(0))))
    scheme = make_scheme(hp, params)
    state = on_chip(jax.eval_shape(scheme.init_state, params))
    shape = (chip_smoke.PHASE_B_BATCH, chip_smoke.PHASE_B_SEQ)
    batch = {k: _sds(shape, jnp.int32, one_chip)
             for k in ("tokens", "labels")}

    def grad_fn(p, b):
        return jax.grad(lambda pp: M.loss_fn(pp, b, cfg)[0])(p)

    step = shared_local_step(scheme, grad_fn, params)
    ma = step.lower(params, state, [batch]).compile().memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert 0 < need < 16 * GIB, need
