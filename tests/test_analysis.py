"""HLO-text cost model (roofline inputs): trip-count-aware flops/bytes/
collective accounting must agree with XLA cost_analysis on loop-free
programs and correct its known while-body undercount on scans — plus
``sim_telemetry_summary`` hardening against sparse/legacy exports."""
import json

import jax
import jax.numpy as jnp
import pytest

from repro.launch import analysis
from repro.launch.analysis import sim_telemetry_summary


def test_scan_flops_weighted_by_trip_count():
    def f(x):
        def body(c, _):
            return c @ c * 0.5 + c, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    r = analysis.hlo_costs(c.as_text())
    expected = 2 * 64 ** 3 * 7
    assert abs(r["flops"] - expected) / expected < 0.05
    # cost_analysis undercounts (counts the body once) — that's the bug
    # this parser exists to fix
    assert c.cost_analysis()["flops"] < 0.5 * expected


def test_matches_cost_analysis_on_loop_free_program():
    def g(a, b):
        return jax.nn.relu(a @ b) @ b

    sds = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(g).lower(sds, sds).compile()
    r = analysis.hlo_costs(c.as_text())
    ca = c.cost_analysis()
    assert abs(r["flops"] - ca["flops"]) / ca["flops"] < 0.05
    assert abs(r["bytes"] - ca["bytes accessed"]) / ca["bytes accessed"] < 0.2


def test_nested_scan_multiplies():
    def f(x):
        def inner(c, _):
            return c @ c, None

        def outer(c, _):
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None

        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    r = analysis.hlo_costs(c.as_text())
    expected = 2 * 32 ** 3 * 15
    assert abs(r["flops"] - expected) / expected < 0.05


def test_collective_bytes_parse():
    hlo = """
HloModule m

ENTRY %main (p: f32[16,16]) -> f32[64,16] {
  %p = f32[16,16]{1,0} parameter(0)
  ROOT %ag = f32[64,16]{1,0} all-gather(%p), replica_groups={{0,1,2,3}}, dimensions={0}
}
"""
    r = analysis.hlo_costs(hlo)
    assert r["collectives"]["all-gather"] == 64 * 16 * 4
    old = analysis.collective_bytes(hlo)
    assert old["all-gather"] == 64 * 16 * 4


def test_shape_bytes():
    assert analysis._shape_bytes("f32[2,3]{1,0}") == 24
    assert analysis._shape_bytes("(bf16[8], s32[2,2])") == 32
    assert analysis._shape_bytes("pred[]") == 1


# ------------------------------------------- sim_telemetry_summary

def test_sim_summary_zero_rounds():
    s = sim_telemetry_summary({"scenario": "empty", "seed": 3,
                               "rounds": [], "summary": {"rounds": 0}})
    assert s["scenario"] == "empty" and s["seed"] == 3
    assert s["min_honest_share"] is None
    assert s["honest_majority_all_rounds"] is False
    assert s["network_drops"] == 0
    assert s["audit_flagged_peers"] == []
    assert s["audit_flagged_final_share"] == 0
    assert "mean_stage_ms" not in s


def test_sim_summary_missing_fields_degrade_to_unknown():
    # legacy / hand-built rounds: no audit, val_loss, fast_pass_rate,
    # network, consensus — and one round with no honest_share at all
    rounds = [
        {"round": 0, "honest_share": 0.8,
         "consensus": {"a": 0.6, "bad": 0.4}},
        {"round": 1},
    ]
    s = sim_telemetry_summary({"rounds": rounds})
    assert s["min_honest_share"] == 0.8
    assert s["honest_majority_all_rounds"] is True
    assert s["audit_flagged_peers"] == []
    # flagged share over the LAST round's consensus (absent here)
    assert s["audit_flagged_final_share"] == 0


def test_sim_summary_audit_fallback_from_rounds():
    # pre-audit exports carry no summary.audit_flagged_peers: the flagged
    # set is rebuilt from the per-round audit verdicts
    rounds = [
        {"round": 0, "honest_share": 0.9,
         "audit": {"val-0": {"bad": "loss_mismatch"}},
         "consensus": {"a": 0.7, "bad": 0.3}},
    ]
    s = sim_telemetry_summary({"rounds": rounds, "summary": {}})
    assert s["audit_flagged_peers"] == ["bad"]
    assert s["audit_flagged_final_share"] == pytest.approx(0.3)


def test_sim_summary_path_vs_dict_parity(tmp_path):
    tel = {"scenario": "parity", "seed": 1,
           "rounds": [{"round": 0, "honest_share": 0.75,
                       "network": {"dropped": 2},
                       "consensus": {"a": 1.0}}],
           "summary": {"rounds": 1, "final_honest_share": 0.75}}
    p = tmp_path / "tel.json"
    p.write_text(json.dumps(tel))
    assert sim_telemetry_summary(str(p)) == sim_telemetry_summary(tel)
    assert sim_telemetry_summary(tel)["network_drops"] == 2


def test_sim_summary_mean_stage_ms_from_perf():
    tel = {"rounds": [{"round": 0, "honest_share": 1.0}],
           "perf": [
               {"round": 0, "stage_ms": {"val-0": {"fast_filter": 2.0,
                                                   "aggregate": 10.0}}},
               {"round": 1, "stage_ms": {"val-0": {"fast_filter": 4.0},
                                         "val-1": {"fast_filter": 6.0}}},
           ]}
    s = sim_telemetry_summary(tel)
    assert s["mean_stage_ms"] == {"aggregate": 10.0, "fast_filter": 4.0}
