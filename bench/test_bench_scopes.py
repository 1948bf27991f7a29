"""Device self time split by the program's named scopes (``scopecut``):
the classifier on the ``op_name`` forms JAX writes, self time on hand-
made planes, and both on a small trace recorded on a TPU v5e with its
program's HLO (``bench/data/record_scoped_trace.py``)."""
import os
from types import SimpleNamespace as NS

import pytest

import scopecut
import tracecut

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 0, 1000), _ev("bench.step", 50, 940),
        _ev("gauntlet.stage.primary_eval", 900, 100)])])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev("%while.1 = (f32[]) while(...)", 100, 800),
        _ev("%fusion.2 = f32[8] fusion(...)", 150, 250),
        _ev("%fusion.3 = f32[8] fusion(...)", 450, 350),
        _ev("%copy.4 = f32[8] copy(...)", 920, 50)])])
    return [host, dev]


NAMES = {
    "while.1": "jit(step)/model.accumulate/while",
    "fusion.2": "jit(step)/model.accumulate/while/body/closed_call/"
                "jvp(model)/while/body/closed_call/dot_general",
    "fusion.3": "jit(step)/model.accumulate/while/body/closed_call/"
                "transpose(jvp(model))/while/body/closed_call/checkpoint/"
                "rematted_computation/dot_general",
}


@pytest.mark.parametrize("op_name,cls", [
    ("jit(step)/jvp(model)/dot_general", "fwd"),
    ("jit(step)/transpose(jvp(model))/while/body/closed_call/checkpoint/"
     "rematted_computation/dot_general", "bwd"),
    ("jit(step)/model.accumulate/while/body/add", "bwd"),
    ("jit(step)/model/dot_general", "fwd"),
    ("jit(step)/shard_map/demo.encode/demo.encode/dot_general", "encode"),
    ("jit(step)/shard_map/demo.topk/top_k", "topk"),
    ("jit(step)/shard_map/demo.decode/demo.decode/scatter", "decode"),
    ("jit(step)/demo.apply/demo.decode/dot_general", "decode"),
    ("jit(step)/demo.apply/sign", "apply"),
    ("jit(step)/transpose(jvp(model))/mul;transpose(jvp(model))/"
     "broadcast_in_dim", "bwd"),
    ("jit(model)/add", "unscoped"),
    ("jit(step)/all_gather", "unscoped"),
    ("reduce_sum", "unscoped"),
    (None, "unscoped"),
])
def test_scope_class(op_name, cls):
    assert scopecut.scope_class(op_name) == cls


def test_op_names_from_hlo_text():
    """Own metadata first; else a consumer's (a broadcast of a
    constant); else a callee's (a pass's fusion) or an operand's (a
    sort); a reducer's relative name is no stage's."""
    text = """\
%region.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.6 = f32[] add(%a, %b), metadata={op_name="scatter-add"}
}

%fused_computation.3 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %scatter.4 = f32[8]{0} scatter(%param_0), to_apply=%region.1
  %mul.5 = f32[8]{0} multiply(%param_0), metadata={op_name="jit(f)/demo.decode/mul"}
}

ENTRY %main.9 (p: f32[8]) -> (f32[8]) {
  %p = f32[8]{0} parameter(0)
  %constant.1 = f32[] constant(0)
  %broadcast.9 = f32[8]{0} broadcast(%constant.1), dimensions={}
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %p, %broadcast.9), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(f)/demo.topk/abs" source_file="x.py" source_line=3}
  %fusion.8 = f32[8]{0} fusion(%p), kind=kCustom, calls=%fused_computation.3
  %sort.2 = (s32[8]{0}, f32[8]{0}) sort(%p, %fusion.7), to_apply=%compare.1
  ROOT %tuple.1 = (f32[8]{0}) tuple(%fusion.7)
}
"""
    names = scopecut.op_names(text)
    assert "add.6" not in names
    assert {k: names[k] for k in ("mul.5", "fusion.7", "fusion.8",
                                  "broadcast.9", "sort.2")} == {
        "mul.5": "jit(f)/demo.decode/mul",
        "fusion.7": "jit(f)/demo.topk/abs",
        "fusion.8": "jit(f)/demo.decode/mul",
        "broadcast.9": "jit(f)/demo.topk/abs",
        "sort.2": "jit(f)/demo.topk/abs"}


def test_self_time_on_hand_made_trace():
    planes = _planes()
    selfs = scopecut.self_times(planes)
    # the while's own 200 ns are its gaps around two body fusions
    assert selfs == {"while.1": pytest.approx(200e-9),
                     "fusion.2": pytest.approx(250e-9),
                     "fusion.3": pytest.approx(350e-9),
                     "copy.4": pytest.approx(50e-9)}
    busy = tracecut.reduce(planes)["busy_s"]
    assert sum(selfs.values()) == pytest.approx(busy)
    classes = scopecut.by_class(selfs, NAMES)
    assert classes == {"fwd": pytest.approx(250e-9),
                       "bwd": pytest.approx(550e-9),
                       "encode": 0.0, "topk": 0.0, "decode": 0.0,
                       "apply": 0.0, "unscoped": pytest.approx(50e-9)}


def test_self_time_of_overlap_without_nesting():
    ops = [("a", 0, 100), ("b", 50, 150), ("c", 60, 70)]
    assert scopecut.self_ns(ops, 0, 120) == {"a": 50, "b": 60, "c": 10}


def test_gaps_named_by_bench_or_gauntlet_span():
    # idle 0..100 under step, 900..920 and 970..1000 under the stage
    assert scopecut.named_gaps(_planes()) == [
        ["step", pytest.approx(100e-9)],
        ["gauntlet.stage.primary_eval", pytest.approx(30e-9)],
        ["gauntlet.stage.primary_eval", pytest.approx(20e-9)]]
    assert scopecut.named_gaps(_planes(), prefixes=("bench.",))[1][0] == \
        "step"


def test_named_gaps_read_as_reduce_on_recorded_trace():
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(
        os.path.join(DATA, "v5e_small.xplane.pb")).planes)
    assert scopecut.named_gaps(planes) == tracecut.reduce(planes)[
        "idle_gaps"]


def test_recorded_scoped_trace():
    """5 steps of a scanned matmul under ``model``, its gradient and a
    top-k under ``demo.topk`` (record_scoped_trace.py): every class the
    program has reads above zero, and the self times add up to the
    busy time."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(
        os.path.join(DATA, "v5e_scoped.xplane.pb")).planes)
    with open(os.path.join(DATA, "v5e_scoped.hlo.txt")) as f:
        names = scopecut.op_names(f.read())
    busy = tracecut.reduce(planes)["busy_s"]
    selfs = scopecut.self_times(planes)
    assert sum(selfs.values()) == pytest.approx(busy, rel=1e-9)
    classes = scopecut.by_class(selfs, names)
    assert all(classes[c] > 0 for c in ("fwd", "bwd", "topk")), classes
    assert classes["unscoped"] < 0.1 * busy, classes
