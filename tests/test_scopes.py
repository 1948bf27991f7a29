"""Named scopes of the peer's DeMo step (``repro.obs.trace.SCOPE_*``):
every stage of the compiled step owns instructions under the
benchmark's classifier (``bench/scopecut.py``), and the scopes change no
number of the step."""
import contextlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import InputShape, TrainConfig
from repro.configs.registry import tiny_config
from repro.data.pipeline import synthetic_batch
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_step
from repro.obs import trace

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))
import scopecut  # noqa: E402

STAGES = ("fwd", "bwd", "encode", "topk", "decode", "apply")


def _compiled_step():
    """The tiny DeMo step as the peer cells build it: scanned layers,
    remat, donation, two micro-batches, one peer on a host mesh."""
    cfg = tiny_config(peer_axes=("data",))
    hp = TrainConfig(demo_chunk=16, demo_topk=4)
    mesh = make_host_mesh(data=1)
    shape = InputShape("t", seq_len=32, global_batch=4, kind="train")
    plan = make_step(cfg, hp, mesh, shape, variant="demo", microbatch=2,
                     scan_layers=True)
    return cfg, mesh, plan, plan.lower(mesh).compile()


@pytest.fixture(scope="module")
def scoped():
    return _compiled_step()


@pytest.fixture(scope="module")
def unscoped():
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        return _compiled_step()
    finally:
        jax.named_scope = real


def test_vocabulary_matches_the_benchmark():
    scopes = {v for k, v in vars(trace).items() if k.startswith("SCOPE_")}
    assert scopes == set(scopecut.CLASS_OF_SCOPE)


def test_every_stage_owns_instructions(scoped):
    names = scopecut.op_names(scoped[3].as_text())
    owned = {c: 0 for c in scopecut.CLASSES}
    for op_name in names.values():
        owned[scopecut.scope_class(op_name)] += 1
    assert all(owned[c] > 0 for c in STAGES), owned


def _instructions(hlo_text):
    """The module's instruction lines without their metadata."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in hlo_text.splitlines()
            if re.match(r"\s*(ROOT\s+)?%|ENTRY", line)]


def test_scopes_change_no_number(scoped, unscoped):
    cfg, mesh, plan, step = scoped
    step0 = unscoped[3]
    assert not any(scopecut.scope_class(n) in STAGES for n in
                   scopecut.op_names(step0.as_text()).values())
    assert _instructions(step.as_text()) == _instructions(step0.as_text())
    key = jax.random.PRNGKey(0)
    batch = synthetic_batch(key, cfg.vocab_size, 4, 32, cfg)

    def fresh():
        params = jax.tree.map(
            lambda s: jax.random.normal(key, s.shape, s.dtype) * 0.02,
            plan.args[0])
        ef = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          plan.args[1])
        return params, ef

    with jax.set_mesh(mesh):
        outs = []
        for fn in (step, step0):
            params, ef = fresh()
            for i in range(2):
                params, ef, loss = fn(params, ef, batch, np.int32(i))
            outs.append(jax.device_get((params, ef, loss)))
    for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        np.testing.assert_array_equal(a, b)
