"""Distributed step semantics on a forced 16-device host platform.

Runs in a SUBPROCESS so the parent pytest process keeps its single CPU
device (XLA device count is locked at first jax init)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import dataclasses
    import json
    import jax, jax.numpy as jnp
    from repro.configs.registry import tiny_config
    from repro.configs.base import TrainConfig, InputShape
    from repro.launch.steps import make_ddp_train_step, make_train_step
    from repro.launch import analysis
    from repro.launch.mesh import make_host_mesh

    cfg = tiny_config(num_layers=2, d_model=128, d_ff=256, vocab_size=512
                      ).with_overrides(peer_axes=("data",))
    hp = TrainConfig(learning_rate=1e-2, warmup_steps=0, total_steps=100,
                     demo_chunk=16, demo_topk=8)
    mesh = make_host_mesh(data=4, model=4)
    shape = InputShape("t", seq_len=128, global_batch=8, kind="train")

    # donate=False: this test re-reads `params` after the call (donation
    # is the production default but deletes the input buffers)
    plan = make_train_step(cfg, hp, mesh, shape, remat=False,
                           donate=False)
    compiled = plan.lower(mesh).compile()

    from repro.models.model import init_params
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    K = 4
    ef = jax.tree.map(lambda p: jnp.zeros((K,) + p.shape, p.dtype), params)
    batch = {
        "tokens": jax.random.randint(key, (8, 128), 0, 512),
        "labels": jax.random.randint(key, (8, 128), 0, 512),
    }
    with jax.set_mesh(mesh):
        new_params, new_ef, loss = compiled(params, ef, batch,
                                            jnp.int32(10))
    out = {}
    out["loss_finite"] = bool(jnp.isfinite(loss))
    # params moved by exactly lr * sign pattern
    d = jax.tree.map(lambda a, b: jnp.abs(a - b), params, new_params)
    maxd = max(float(jnp.max(x)) for x in jax.tree.leaves(d))
    out["max_update"] = maxd
    # per-peer EF buffers differ across peers (distinct local batches)
    efw = new_ef["layers"][0]["attn"]["wq"]["w"]
    out["ef_peer_variance"] = float(
        jnp.mean(jnp.var(efw.astype(jnp.float32), axis=0)))
    # collective content: demo step must all-gather, never all-reduce grads
    hlo = compiled.as_text()
    cb = analysis.collective_bytes(hlo)
    out["collectives"] = {k: v for k, v in cb.items()}

    plan2 = make_ddp_train_step(cfg, hp, mesh, shape, remat=False)
    c2 = plan2.lower(mesh).compile()
    cb2 = analysis.collective_bytes(c2.as_text())
    out["ddp_collectives"] = {k: v for k, v in cb2.items()}

    # pure data-parallel mesh isolates CROSS-PEER traffic (the paper's
    # quantity): no TP weight-gathers mixed in.
    mesh_dp = make_host_mesh(data=16)
    shape_dp = InputShape("t", seq_len=128, global_batch=16, kind="train")
    cbd = analysis.collective_bytes(
        make_train_step(cfg, hp, mesh_dp, shape_dp, remat=False)
        .lower(mesh_dp).compile().as_text())
    cbdd = analysis.collective_bytes(
        make_ddp_train_step(cfg, hp, mesh_dp, shape_dp, remat=False)
        .lower(mesh_dp).compile().as_text())
    out["dp_demo_collectives"] = {k: v for k, v in cbd.items()}
    out["dp_ddp_collectives"] = {k: v for k, v in cbdd.items()}

    # one codec: on a 4-peer mesh, with and without a model axis, each
    # peer's new error feedback is the scheme's local_step on that peer's
    # gradient, and the new params are apply_update of the scheme's
    # aggregate of the stacked payloads
    from repro.demo.schedules import warmup_cosine
    from repro.models.model import loss_fn
    from repro.schemes import apply_update, make_scheme
    grad = jax.jit(jax.grad(lambda p, b: loss_fn(p, b, cfg)[0]))
    out["parity"] = {}
    for (name, mesh_name), mesh_p in (
            ((n, m), make_host_mesh(data=4, model=int(m[-1])))
            for n in ("demo", "randk") for m in ("4x1", "4x4")):
        hp_s = dataclasses.replace(hp, scheme=name, randk_frac=0.05)
        plan = make_train_step(cfg, hp_s, mesh_p, shape, remat=False,
                               donate=False)
        ef0 = jax.tree.map(
            lambda s: 0.01 * jax.random.normal(key, s.shape, s.dtype),
            plan.args[1])
        with jax.set_mesh(mesh_p):
            p_mesh, ef_mesh, _ = plan.lower(mesh_p).compile()(
                params, ef0, batch, jnp.int32(10))
        scheme = make_scheme(hp_s, params)
        payloads, ef_ref = [], []
        for k in range(K):
            b = jax.tree.map(lambda x: x[2 * k:2 * k + 2], batch)
            pl, e = scheme.local_step(
                grad(params, b), jax.tree.map(lambda x: x[k], ef0), batch=b)
            payloads.append(pl)
            ef_ref.append(e)
        lr = warmup_cosine(jnp.int32(10), base_lr=hp_s.learning_rate,
                           warmup_steps=hp_s.warmup_steps,
                           total_steps=hp_s.total_steps)
        stacked = scheme.stack_payloads(payloads)
        p_ref = apply_update(params, scheme.aggregate(stacked), lr,
                             weight_decay=hp_s.weight_decay)
        # the payload layout on a mesh is the scheme's own business
        with jax.set_mesh(mesh_p):
            jaxpr = jax.make_jaxpr(scheme.aggregate)(stacked)
        row_specs = sorted({str(e.params["sharding"].spec)
                            for e in jaxpr.eqns
                            if e.primitive.name == "sharding_constraint"})
        ef_err = max(
            float(jnp.max(jnp.abs(a[k] - b)))
            for k in range(K) for a, b in zip(jax.tree.leaves(ef_mesh),
                                               jax.tree.leaves(ef_ref[k])))
        p_err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree.leaves(p_mesh), jax.tree.leaves(p_ref)))
        moved = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree.leaves(p_mesh), jax.tree.leaves(params)))
        out["parity"][f"{name}@{mesh_name}"] = {
            "ef_err": ef_err, "p_err": p_err, "moved": moved,
            "row_specs": row_specs}
    print("RESULT::" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT::")][0]
    return json.loads(line[len("RESULT::"):])


def test_demo_step_runs_and_updates(result):
    assert result["loss_finite"]
    # signed update: |Δθ| <= lr (+ weight decay drift)
    assert 0 < result["max_update"] < 0.02


def test_per_peer_error_feedback_distinct(result):
    assert result["ef_peer_variance"] > 0


def test_demo_step_gathers_compressed_not_allreduce_grads(result):
    c = result["collectives"]
    assert c["all-gather"] > 0
    # the paper's point: collective volume is dominated by the compressed
    # payload gather, not by dense-gradient all-reduce. TP activations
    # still all-reduce; they must not dwarf the DDP grad reduction below.
    ddp = result["ddp_collectives"]
    assert ddp["all-reduce"] > c["all-reduce"]


def test_demo_collective_bytes_beat_ddp(result):
    """Paper §2/§5: cross-peer traffic (isolated on a pure-DP mesh) must
    be far smaller for compressed payload gathers than dense grad
    reduction. On the TP mesh, weight-gathers common to both variants
    dominate at toy scale — the dp mesh is the honest comparison."""
    demo_total = sum(v for k, v in result["dp_demo_collectives"].items()
                     if k != "count")
    ddp_total = sum(v for k, v in result["dp_ddp_collectives"].items()
                    if k != "count")
    assert demo_total < ddp_total, (demo_total, ddp_total)


@pytest.mark.parametrize(
    "scheme,mesh", [("demo", "4x1"), ("randk", "4x1"), ("demo", "4x4"),
                    ("randk", "4x4")],
    ids=["demo", "randk", "demo-4x4", "randk-4x4"])
def test_mesh_step_runs_the_schemes_codec(result, scheme, mesh):
    """One codec: ``make_train_step`` on a 4-peer mesh, with a model axis
    of 1 or 4 devices, gives each peer the error feedback
    ``scheme.local_step`` gives on that peer's gradient, and params equal
    to ``apply_update`` of ``scheme.aggregate`` over the four payloads,
    weight decay included."""
    r = result["parity"][f"{scheme}@{mesh}"]
    assert r["moved"] > 0
    assert r["ef_err"] < 1e-5, r
    assert r["p_err"] < 1e-6, r


@pytest.mark.parametrize("scheme,rows", [
    ("demo", ["PartitionSpec(None, ('model', 'data'), None)"]),
    ("randk", [])], ids=["demo", "randk"])
def test_payload_layout_belongs_to_the_scheme(result, scheme, rows):
    """Under a mesh, DeMo's ``aggregate`` shards the gathered chunk rows
    over the free mesh axes, 'model' first; rand-k's payload rows are
    random flat indices, so its ``aggregate`` adds no constraint."""
    for mesh in ("4x1", "4x4"):
        assert result["parity"][f"{scheme}@{mesh}"]["row_specs"] == rows
