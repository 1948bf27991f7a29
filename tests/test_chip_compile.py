"""Compile for one TPU v5e chip that is described, not attached: the
Pallas kernels at real widths, DeepSeek-V2-Lite's latent attention at
the benchmark cell's shapes, and phase B's peer local step of
``chip_smoke.py``. Nothing runs; the TPU compiler refuses what the chip
would refuse (unaligned tiles, too much fast memory, too much HBM).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file."""
import functools
import os
import re
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
def v5e_2x2():
    """The four chips of a described v5e 2x2 host."""
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
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


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


def _phase_b(sharding):
    """Phase B's model, scheme and parameters, the parameters placed by
    ``sharding``, and its loss gradient."""
    sys.path.insert(0, ROOT)
    import chip_smoke
    from repro.models import model as M
    from repro.schemes import make_scheme

    cfg = chip_smoke.templar(chip_smoke.PHASE_B_LAYERS)
    params = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, sharding),
        jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    scheme = make_scheme(chip_smoke.phase_b_hp(), params)

    def grad_fn(p, b):
        return jax.grad(lambda pp: M.loss_fn(pp, b, cfg)[0])(p)

    return scheme, params, grad_fn


def _phase_b_batch(sharding, rows=()):
    sys.path.insert(0, ROOT)
    import chip_smoke
    shape = (*rows, chip_smoke.PHASE_B_BATCH, chip_smoke.PHASE_B_SEQ)
    return {k: _sds(shape, jnp.int32, sharding) for k in ("tokens", "labels")}


@pytest.fixture(scope="module")
def phase_b_step(one_chip):
    """Phase B's peer local step compiled for the described chip, and
    the number of parameter leaves it compresses."""
    from repro.training.peer import shared_local_step

    scheme, params, grad_fn = _phase_b(one_chip)
    state = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                         jax.eval_shape(scheme.init_state, params))
    step = shared_local_step(scheme, grad_fn, params)
    return (step.lower(params, state, [_phase_b_batch(one_chip)]).compile(),
            len(jax.tree.leaves(params)))


def test_phase_b_local_step_fits_one_chip(phase_b_step):
    ma = phase_b_step[0].memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert 0 < need < 16 * GIB, need


def _selections(compiled):
    """Scope class of each Pallas kernel call and of each sort in a
    compiled program; a TopK custom call fails the test."""
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    import scopecut

    text = compiled.as_text()
    names = scopecut.op_names(text)
    kernels, sorts = [], []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+) = .*? "
                     r"(custom-call|sort)\(", line)
        if not m:
            continue
        assert 'custom_call_target="TopK"' not in line, line
        cls = scopecut.scope_class(names.get(m.group(1)))
        if 'custom_call_target="tpu_custom_call"' in line:
            kernels.append(cls)
        elif m.group(2) == "sort":
            sorts.append(cls)
    return kernels, sorts


def test_phase_b_local_step_selects_in_vmem(phase_b_step):
    """On the TPU every parameter leaf's top-k is one Pallas kernel
    call under the ``demo.topk`` scope, and nothing of that scope sorts:
    no XLA TopK, no sort. (The sorts left are the decode's scatters'.)"""
    compiled, leaves = phase_b_step
    kernels, sorts = _selections(compiled)
    assert kernels == ["topk"] * leaves
    assert "topk" not in sorts


@pytest.mark.parametrize("peers", [0, 4], ids=["no-mesh", "peer-mesh-4"])
def test_phase_b_replay_selects_in_vmem(v5e_2x2, one_chip, peers):
    """The validator's replay audit (``shared_replay_step``, every round
    of phase B) vmaps the peer's local step over the audited peers and,
    on a ``make_peer_mesh`` validator, splits them over the peer axis.
    The batched kernel must lower on both: one call per parameter leaf,
    nothing of ``demo.topk`` sorting."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.sharding import PEER_AXIS
    from repro.training.peer import shared_replay_step

    if peers:
        # what make_peer_mesh(4) builds over the four chips
        mesh = Mesh(np.array(v5e_2x2[:peers]), (PEER_AXIS,),
                    axis_types=(jax.sharding.AxisType.Auto,))
        on_params = NamedSharding(mesh, P())
        on_rows = NamedSharding(mesh, P(PEER_AXIS))
    else:
        mesh, on_params, on_rows = None, one_chip, one_chip
    scheme, params, grad_fn = _phase_b(on_params)
    step = shared_replay_step(scheme, grad_fn, params, mesh=mesh)
    batches = _phase_b_batch(on_rows, rows=(max(peers, 2),))
    kernels, sorts = _selections(step.lower(params, batches).compile())
    assert kernels == ["topk"] * len(jax.tree.leaves(params))
    assert "topk" not in sorts


@pytest.mark.parametrize("data,model", [(1, 1), (2, 2), (4, 1), (1, 4)])
def test_mesh_step_selects_in_vmem(v5e_2x2, data, model):
    """The benchmark cells' program at test size: ``make_step``'s DeMo
    step on a (data, model) mesh of the described chips, whose peer
    ``shard_map`` leaves the model axis to the partitioner. The kernel
    must lower there too, on each device's share of the chunk rows: one
    call per stacked parameter leaf."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.base import InputShape, TrainConfig
    from repro.configs.registry import tiny_config
    from repro.launch.steps import make_step

    mesh = Mesh(np.array(v5e_2x2[:data * model]).reshape(data, model),
                ("data", "model"))
    plan = make_step(tiny_config(peer_axes=("data",)),
                     TrainConfig(demo_chunk=16, demo_topk=4), mesh,
                     InputShape("t", seq_len=32, global_batch=8,
                                kind="train"),
                     variant="demo", microbatch=2, scan_layers=True)
    kernels, sorts = _selections(plan.lower(mesh).compile())
    assert kernels == ["topk"] * len(jax.tree.leaves(plan.args[0]))
    assert "topk" not in sorts


def _collectives_by_scope(text):
    """Bytes each scope of the expert layer moves in collectives."""
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    import scopecut

    names = scopecut.op_names(text)
    moved = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+) = (\S+) (all-gather|"
                     r"all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)(?:-start)?\(", line)
        if not m:
            continue
        scope = next((s for s in ("moe.dispatch", "moe.experts",
                                  "moe.combine")
                      if s in (names.get(m.group(1)) or "")), None)
        if scope:
            moved[scope] = moved.get(scope, 0) + 1
    return moved


def test_expert_groups_stay_on_their_shard(v5e_2x2):
    """DeepSeek-V2's peer step on a (data, model) mesh where 'data' splits
    the tokens within one peer: the expert layer sorts, gathers and sums
    back each data shard's tokens on its own device (``num_groups``), so
    its dispatch, products and combine move nothing between chips, and
    each sort holds one shard's assignments. The DeMo top-k lowers to
    one kernel call per parameter leaf, the grouped products to kernels
    of their own."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.base import InputShape, TrainConfig
    from repro.configs.registry import reduced_config
    from repro.launch.steps import make_step

    mesh = Mesh(np.array(v5e_2x2).reshape(2, 2), ("data", "model"))
    cfg = reduced_config("deepseek-v2-lite").with_overrides(
        peer_axes=("pod",))
    plan = make_step(cfg, TrainConfig(demo_chunk=16, demo_topk=4), mesh,
                     InputShape("t", seq_len=64, global_batch=8,
                                kind="train"),
                     variant="demo", microbatch=2, scan_layers=True)
    compiled = plan.lower(mesh).compile()
    kernels, _ = _selections(compiled)
    # the rest are the grouped products' kernels, in the model's scopes
    assert kernels.count("topk") == len(jax.tree.leaves(plan.args[0]))
    assert set(kernels) == {"topk", "fwd", "bwd"}, set(kernels)
    text = compiled.as_text()
    assert _collectives_by_scope(text) == {}
    # a micro-batch of 4 x 64 tokens, top-2: 512 assignments, 256 a shard
    sorted_ints = [m.group(1) for m in (
        re.search(r"= \(s32\[([0-9,]+)\]", line)
        for line in text.splitlines() if " sort(" in line) if m]
    assert sorted_ints and set(sorted_ints) == {"1,256"}, sorted_ints


def _kernel_scopes(text):
    """The ``BLOCK_SCOPES`` scope of each Pallas kernel call in a
    compiled program's text."""
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    import moe_yardstick
    import scopecut

    names = scopecut.op_names(text)
    return [moe_yardstick.block_scope(names.get(m.group(1)))
            for m in (re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+) = .*"
                               r'custom_call_target="tpu_custom_call"', line)
                      for line in text.splitlines()) if m]


@pytest.mark.parametrize("examples", [0, 2], ids=["plain", "vmapped"])
def test_mla_attention_runs_in_vmem(one_chip, examples):
    """One DeepSeek-V2-Lite latent-attention layer at the benchmark
    cell's micro-batch (4 x 4096, 16 heads), forward and backward, and
    the same under ``jax.vmap`` over a leading axis (the validator's
    replay): the causal core is the splash kernel, forward in the
    forward, and forward and the fused backward in the gradient, each
    under scope ``mla``; no float32 score buffer of the query-blocked
    path is left."""
    from repro.configs.registry import get_config
    from repro.models import mla

    cfg = get_config("deepseek-v2-lite")
    rows = (examples,) if examples else ()
    x = _sds((*rows, 4, 4096, cfg.d_model), jnp.bfloat16, one_chip)
    params = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: mla.init_mla(jax.random.PRNGKey(0), cfg)))

    layer = functools.partial(mla.attend_full, cfg=cfg)
    if examples:
        layer = jax.vmap(layer, (None, 0))

    def loss(p, x):
        return jnp.sum(layer(p, x).astype(jnp.float32))

    fwd = jax.jit(layer).lower(params, x).compile().as_text()
    bwd = jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile().as_text()
    assert _kernel_scopes(fwd) == ["mla"]
    assert _kernel_scopes(bwd) == ["mla"] * 2
    for text in (fwd, bwd):
        assert not re.search(r"f32\[(?:\d+,)*4,16,512,4096\]", text)


@pytest.mark.parametrize("data,model", [(1, 1), (2, 2)])
def test_mesh_step_runs_mla_in_vmem(v5e_2x2, data, model):
    """DeepSeek-V2-Lite's heads at their published widths in
    ``make_step``'s DeMo step at 1,024 tokens, the benchmark cell's
    program, on a (data, model) mesh where 'data' splits the tokens
    within one peer: inside the peer ``shard_map``, the layer scan and
    the remat, each layer's attention core lowers to the splash kernel,
    its examples over 'data' and its heads over 'model' (forward; and
    forward again and the fused backward in the gradient), under scope
    ``mla``."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.base import InputShape, TrainConfig
    from repro.configs.registry import reduced_config
    from repro.launch.steps import make_step

    mesh = Mesh(np.array(v5e_2x2[:data * model]).reshape(data, model),
                ("data", "model"))
    cfg = reduced_config("deepseek-v2-lite")
    cfg = cfg.with_overrides(peer_axes=("pod",), mla=dataclasses.replace(
        cfg.mla, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128))
    plan = make_step(cfg, TrainConfig(demo_chunk=16, demo_topk=4), mesh,
                     InputShape("t", seq_len=1024, global_batch=4,
                                kind="train"),
                     variant="demo", microbatch=2, scan_layers=True)
    scopes = _kernel_scopes(plan.lower(mesh).compile().as_text())
    assert scopes.count("mla") == 3 * cfg.num_layers, scopes
