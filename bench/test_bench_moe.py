"""The DeepSeek-V2-Lite cell's pieces at test size on the CPU: a run of
the ``peer_step_moe`` entry is correct and each planted fault and the
float8 control are not, under the cell's own limits; the FLOPs it
counts; the block's scope vocabulary and the readers of its metrics."""
import json
import os
import shutil
import time

import jax
import pytest

import compare
import faults
import harness
import moe_yardstick
import tinycell

CELL = "tinymoe.train"
REAL = "deepseek-v2-lite.peer-moe-4k"
METRICS = ("mfu.peer.moe", "device_ms.peer.mla", "device_ms.peer.moe",
           "moe_experts_roofline")


def _real_config():
    with open(os.path.join(tinycell.BENCH, "configs",
                           "deepseek-v2-lite.json")) as f:
        return json.load(f)


def make_root(tmp: str) -> str:
    """A checkout root whose ``BENCHMARK.json`` holds the one cell
    ``tinymoe.train``: the DeepSeek-V2-Lite block at width 64, 3 layers,
    holding 4 of 16 experts from the fifth, with the real cell's limits."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(tinycell.BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    b = os.path.join(root, "bench")
    c = _real_config()
    c.update(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, kv_lora_rank=32, intermediate_size=128,
             moe_intermediate_size=32, router_outputs=16, n_routed_experts=4,
             expert_offset=4, num_experts_per_tok=4, vocab_size=250,
             logit_rows=256, compute_dtype="float32")
    with open(os.path.join(tinycell.BENCH, "traffic", "peer-moe-4k.json")) as f:
        t = json.load(f)
    t.update(batch=4, seq=32, microbatch=2, pool=4, demo_chunk=16,
             demo_topk=8)
    shutil.copy(os.path.join(b, "checks", REAL + ".json"),
                os.path.join(b, "checks", CELL + ".json"))
    with open(os.path.join(tinycell.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "tinymoe", "source": "test",
                        "file": "bench/configs/tinymoe.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": CELL, "config": "tinymoe",
                          "traffic": "train", "chips": 1, "why": "test"}]
    spec["per_layer"] = [dict(m, workloads=[CELL]) for m in spec["per_layer"]
                         if m["name"] in METRICS]
    for path, obj in ((os.path.join(b, "configs", "tinymoe.json"), c),
                      (os.path.join(b, "traffic", "train.json"), t),
                      (os.path.join(root, "BENCHMARK.json"), spec)):
        with open(path, "w") as f:
            json.dump(obj, f)
    return root


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return harness.find_cell(CELL, make_root(str(tmp_path_factory.mktemp(
        "moe"))))


def _run(cell, step_fault=None):
    out = harness.entry(cell).run(
        cell, seed=2 ** 35 + 3, seconds=0.3, trace=False,
        t_start=time.perf_counter(), devices=jax.devices(),
        step_fault=step_fault)
    return harness.result(cell, out, traced=False)


def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"peer_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [faults.stale_state, faults.half_batch,
                                   faults.negated_update],
                         ids=["state_unchanged", "half_batch",
                              "negated_update"])
def test_fault_is_not_correct(cell, fault):
    res = _run(cell, step_fault=fault)
    assert not res["correct"], res["checks"]


def test_fp8_control_fails_the_limits(cell):
    prog = harness.entry(cell).Program(cell)
    got = prog.reference(cell, 9, "fp8")
    ref = prog.reference(cell, 9, "float32")
    assert not all(harness.passed(v, lim) for _, v, lim in compare.checks(
        compare.gaps(got, ref), cell.limits))


def test_flops_of_the_cell():
    """Forward model FLOPs per token at 4,096 positions, by part: the
    dense layer 162 M, the four expert layers' projections and experts
    302 M (held experts at 0.75 of a pick per token), attention products
    105 M, logits 52 M."""
    c = _real_config()
    per_token = moe_yardstick.forward_flops(c, 1, 4096) / 4096
    assert per_token / 1e6 == pytest.approx(162.0 + 301.4 + 104.9 + 52.4,
                                            abs=0.5)
    step = moe_yardstick.train_step_flops(c, 8, 4096)
    assert step / 1e12 == pytest.approx(61.0, abs=0.2)
    work = moe_yardstick.grouped_work(c, rows=1000.0, layer_batches=2)
    assert work["flops"] == 12 * 2 * 1000 * 2048 * 1408
    assert work["bytes"] == 12 * 2 * (2 * 8 * 2048 * 1408
                                      + 1000 * (2048 + 1408))


def test_block_vocabulary_matches_the_program():
    from repro.obs import trace
    assert moe_yardstick.BLOCK_SCOPES == trace.BLOCK_SCOPES


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/jit(main)/model/mla/dot_general", "mla"),
    ("jit(step)/transpose(jvp(model))/transpose(jvp(moe.experts))/x",
     "moe.experts"),
    ("jit(step)/model/moe.dispatch/sort;jit(step)/model/mla/add",
     "moe.dispatch"),
    ("jit(step)/model.accumulate/model/dot_general", None),
    (None, None)])
def test_block_scope_is_the_innermost(op_name, scope):
    assert moe_yardstick.block_scope(op_name) == scope


def test_readers_read_the_traced_context(cell):
    ctx = {"window_s": 2.0, "busy_s": 1.9, "steps": 4,
           "flops_per_step": 98.5e12, "peak_flops": 197e12,
           "peak_bw": 819e9, "held_rows": 5e4,
           "block_ms": dict.fromkeys(moe_yardstick.BLOCK_SCOPES, 1.0),
           "experts": {"flops": 0.197e12, "bytes": 0.41e9}}
    ctx["block_ms"]["moe.experts"] = 2.0
    read = {m: harness.metric_reader(cell, m).read(ctx) for m in METRICS}
    assert read["mfu.peer.moe"] == pytest.approx(100.0)
    assert read["device_ms.peer.mla"] == 1.0
    assert read["device_ms.peer.moe"] == 5.0      # route+dispatch+experts+combine
    assert read["moe_experts_roofline"] == pytest.approx(50.0)
    for m in METRICS:
        assert harness.metric_reader(cell, m).read(
            {"window_s": 1.0, "busy_s": 1.0, "steps": 1}) is None
