"""Compiled-artifact analysis: collective bytes from HLO text + the
three-term roofline (deliverable g).

    compute    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory     = HLO_bytes / (chips * HBM_bw)
    collective = collective_bytes / (chips * link_bw)

cost_analysis() reports *global* flops/bytes for the SPMD program (per-
device values times... empirically on the CPU backend it reports the
per-module numbers for one partition); we normalize per chip explicitly
from the mesh size so the terms are per-chip seconds either way.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional

import numpy as np

from repro.launch import mesh as mesh_mod

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# matches e.g. "f32[2374,24,64]{2,1,0}" or "bf16[8,4096]"
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(txt: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


# ------------------------------------------------------- HLO cost model
#
# ``compiled.cost_analysis()`` counts a while-loop body ONCE, so any
# scan-over-layers program (the production compile path) under-reports
# flops/bytes/collectives by ~num_layers. We therefore re-derive all
# three from the optimized HLO text, weighting every instruction by the
# product of enclosing ``known_trip_count`` values.

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\(?[^=]*?)\s*"
                     r"([\w\-]+)\((.*)$")
_COMMENT_RE = re.compile(r"/\*.*?\*/")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_TRIP_RE = re.compile(r'known_trip_count[":{\s]+n["\s:]+(\d+)')
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w\.\-]+)")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_FREE_OPS = ("parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "after-all", "iota", "partition-id", "replica-id")


def _dims(shape_txt: str):
    m = _SHAPE_RE.search(shape_txt)
    if not m:
        return None
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


def parse_hlo_module(hlo_text: str):
    """-> (computations: name -> [instr dicts], shapes: name -> shape txt,
    entry computation name or None)."""
    comps: Dict[str, list] = {}
    shapes: Dict[str, str] = {}
    cur = None
    entry = None
    for raw in hlo_text.splitlines():
        line = _COMMENT_RE.sub("", raw).strip()
        if cur is None:
            m = _COMP_RE.match(line)
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, result_txt, op, rest = m.groups()
        shapes[name] = result_txt
        comps[cur].append({"name": name, "op": op, "result": result_txt,
                           "line": line, "rest": rest})
    return comps, shapes, entry


def _instr_flops(ins, shapes) -> float:
    """dot flops = 2 * prod(result dims) * prod(contracted dims)."""
    if ins["op"] != "dot":
        return 0.0
    res = _dims(ins["result"])
    if res is None:
        return 0.0
    m = _CONTRACT_RE.search(ins["line"])
    ops = _OPERAND_RE.findall(ins["rest"].split("),")[0] + ")")
    if not m or not ops:
        return 0.0
    lhs_shape = _dims(shapes.get(ops[0], ""))
    if lhs_shape is None:
        return 0.0
    contracted = 1
    for d in (m.group(1).split(",") if m.group(1) else []):
        contracted *= lhs_shape[int(d)]
    return 2.0 * float(np.prod(res or [1])) * contracted


def _instr_bytes(ins, shapes) -> float:
    """bytes accessed = result + operands (fusion internals are free)."""
    if ins["op"] in _FREE_OPS:
        return 0.0
    total = _shape_bytes(ins["result"])
    arg_txt = ins["rest"].split("),")[0]
    for op_name in _OPERAND_RE.findall(arg_txt):
        if op_name in shapes:
            total += _shape_bytes(shapes[op_name])
    return float(total)


def _instr_collective(ins) -> Optional[str]:
    op = ins["op"]
    if op.endswith("-done"):
        return None
    for c in _COLLECTIVES:
        if op == c or op.startswith(c + "-"):
            return c
    return None


def hlo_costs(hlo_text: str, entry: Optional[str] = None) -> Dict:
    """Trip-count-aware flops / bytes / collective bytes from HLO text."""
    comps, shapes, parsed_entry = parse_hlo_module(hlo_text)
    if not comps:
        return {"flops": 0.0, "bytes": 0.0,
                "collectives": {k: 0.0 for k in _COLLECTIVES},
                "collective_count": 0}
    entry = entry or parsed_entry or next(iter(comps))

    flops = 0.0
    byts = 0.0
    coll = {k: 0.0 for k in _COLLECTIVES}
    count = 0
    seen_stack = []

    def walk(comp: str, mult: float):
        nonlocal flops, byts, count
        if comp in seen_stack:          # defensive: no recursion
            return
        seen_stack.append(comp)
        for ins in comps.get(comp, ()):
            op = ins["op"]
            if op == "while":
                m = _TRIP_RE.search(ins["line"])
                trips = float(m.group(1)) if m else 1.0
                bm = _BODY_RE.search(ins["line"])
                if bm:
                    walk(bm.group(1), mult * trips)
                continue
            if op in ("call", "conditional"):
                for cm in _CALLS_RE.finditer(ins["line"]):
                    walk(cm.group(1), mult)
                continue
            if op == "fusion":
                # fusion body: count dots inside (rare on CPU), bytes from
                # the fusion op itself below
                fm = _CALLS_RE.search(ins["line"])
                if fm:
                    for sub in comps.get(fm.group(1), ()):
                        flops += mult * _instr_flops(sub, shapes)
            flops += mult * _instr_flops(ins, shapes)
            byts += mult * _instr_bytes(ins, shapes)
            c = _instr_collective(ins)
            if c is not None:
                coll[c] += mult * _shape_bytes(ins["result"])
                count += 1
        seen_stack.pop()

    walk(entry, 1.0)
    return {"flops": flops, "bytes": byts, "collectives": coll,
            "collective_count": count}


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes of every collective op in the HLO, by kind.

    Each line looks like:
        %ag = bf16[32,1187,24]{...} all-gather(...), replica_groups=...
    For tuples the result is '( shape, shape )'. We take the bytes of the
    op *result* — for all-gather that is the gathered output, for
    all-reduce the reduced tensor, a reasonable wire-cost proxy.
    """
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        line = _COMMENT_RE.sub("", line).strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(",
                     line)
        if not m:
            continue
        result_txt, opname = m.groups()
        base = None
        for c in _COLLECTIVES:
            if opname == c or opname.startswith(c + "-"):
                base = c
                break
        if base is None:
            continue
        # ignore the *-start/*-done split: count only starts (results match)
        if opname.endswith("-done"):
            continue
        out[base] += _shape_bytes(result_txt)
        out["count"] += 1
    return out


@dataclasses.dataclass
class Roofline:
    """Three-term roofline. cost_analysis() on an SPMD module reports the
    PER-PARTITION program (verified empirically: a 4-way-sharded matmul
    reports 1/4 of the global flops), and the post-SPMD HLO text is the
    per-device program, so all _gflops/_gbytes fields here are per chip;
    ``global_*`` properties scale by the mesh size."""
    arch: str
    shape: str
    mesh: str
    variant: str
    chips: int
    device_kind: str             # keys launch.mesh.PEAKS
    hlo_gflops: float            # per chip
    hlo_gbytes: float            # per chip
    collective_gbytes: float     # per chip
    collective_breakdown: Dict[str, float]
    model_gflops: float          # 6*N(_active)*D analytic, GLOBAL
    peak_bytes_per_chip: float   # from memory_analysis
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0

    def finalize(self):
        peaks = mesh_mod.device_peaks(self.device_kind)
        self.compute_s = self.hlo_gflops * 1e9 / peaks.flops_bf16
        self.memory_s = self.hlo_gbytes * 1e9 / peaks.hbm_bw
        self.collective_s = self.collective_gbytes * 1e9 / peaks.ici_bw
        return self

    @property
    def global_gflops(self) -> float:
        return self.hlo_gflops * self.chips

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        g = self.global_gflops
        return self.model_gflops / g if g else 0.0

    def to_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d["dominant"] = self.dominant
        d["useful_flops_ratio"] = self.useful_flops_ratio
        d["global_gflops"] = self.global_gflops
        return d


def analyze(compiled, lowered, *, arch: str, shape_name: str, mesh_name: str,
            variant: str, chips: int, model_flops: float,
            device_kind: str) -> Roofline:
    ca = compiled.cost_analysis() or {}
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    # trip-count-aware text cost model (cost_analysis counts while bodies
    # once — fatal for the scan-over-layers production path)
    hc = hlo_costs(hlo)
    flops = max(float(ca.get("flops", 0.0)), hc["flops"])
    byts = max(float(ca.get("bytes accessed", 0.0)), hc["bytes"])
    coll = {k: int(v) for k, v in hc["collectives"].items()}
    coll["count"] = hc["collective_count"]
    coll_total = sum(v for k, v in coll.items() if k != "count")
    mem = compiled.memory_analysis()
    peak = float(getattr(mem, "temp_size_in_bytes", 0)
                 + getattr(mem, "argument_size_in_bytes", 0))
    r = Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, variant=variant,
        chips=chips, device_kind=device_kind,
        hlo_gflops=flops / 1e9,
        hlo_gbytes=byts / 1e9,
        collective_gbytes=coll_total / 1e9,
        collective_breakdown={k: v / 1e9 for k, v in coll.items()
                              if k != "count"},
        model_gflops=model_flops / 1e9,
        peak_bytes_per_chip=peak)
    return r.finalize()


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D for training (N active params, D tokens),
    2·N·D for a forward-only step; decode: D = global_batch tokens."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n * toks
    if shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n * toks
    return 2.0 * n * shape.global_batch   # one token per sequence


def save_report(r: Roofline, path: str):
    import os
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(r.to_dict(), f, indent=2)


# ------------------------------------------------- testnet sim telemetry
#
# The discrete-event simulator (repro.sim) exports per-round telemetry
# JSON; these helpers turn an export (path or already-loaded dict) into
# the summary table the scenario CI job and notebooks consume.


def load_sim_telemetry(path: str) -> Dict:
    from repro.sim.telemetry import Telemetry
    return Telemetry.load(path)


def sim_telemetry_summary(telemetry) -> Dict:
    """Headline numbers for one scenario run.

    ``telemetry`` is a path or the dict from ``Telemetry.to_dict()``.
    The per-round reductions come from the export's embedded ``summary``
    (one implementation, in ``repro.sim.telemetry``); this adds the
    cross-round claims the CI job checks — ``honest_majority_all_rounds``
    is the paper's survival claim in one bool: honest peers hold >50% of
    consensus incentive in every round.
    """
    tel = (load_sim_telemetry(telemetry) if isinstance(telemetry, str)
           else telemetry)
    rounds = tel.get("rounds") or []
    base = dict(tel.get("summary") or {})
    # rounds may predate a field (older exports, hand-built dicts):
    # missing honest_share / val_loss / fast_pass_rate must degrade to
    # "unknown", never KeyError (tests/test_analysis.py pins this)
    shares = [r.get("honest_share") for r in rounds]
    shares = [s for s in shares if s is not None]
    # audit verdicts (repro.audit): the flagged share of consensus
    # incentive in the final round — the "copies earn ~0" economics
    # claim in one number. The flagged set itself comes from the
    # embedded summary (one derivation, in repro.sim.telemetry), with a
    # fallback for pre-audit telemetry exports.
    flagged = base.get("audit_flagged_peers")
    if flagged is None:
        flagged = sorted({uid for r in rounds
                          for per_val in (r.get("audit") or {}).values()
                          for uid in per_val})
    last_consensus = rounds[-1].get("consensus", {}) if rounds else {}
    flagged_share = sum(w for p, w in last_consensus.items()
                        if p in flagged)
    base.update({
        "scenario": tel.get("scenario"),
        "seed": tel.get("seed"),
        "min_honest_share": min(shares) if shares else None,
        "honest_majority_all_rounds": bool(shares)
        and all(s > 0.5 for s in shares),
        "network_drops": sum((r.get("network") or {}).get("dropped", 0)
                             for r in rounds),
        "audit_flagged_peers": flagged,
        "audit_flagged_final_share": flagged_share,
    })
    # token-economy digest (repro.econ) — only for exports whose rounds
    # carry settled ``econ`` records (pre-econ exports degrade silently)
    econ_rounds = [r["econ"] for r in rounds if r.get("econ")]
    if econ_rounds:
        last_econ = econ_rounds[-1]
        base.update({
            "econ_total_emitted": sum(e.get("emission", 0.0)
                                      for e in econ_rounds),
            "econ_total_burned": sum(e.get("burned", 0.0)
                                     for e in econ_rounds),
            "econ_total_slashed": sum(e.get("slashed", 0.0)
                                      for e in econ_rounds),
            "econ_final_supply": last_econ.get("supply"),
            "econ_flagged_final_balance": {
                uid: (last_econ.get("balances") or {}).get(uid)
                for uid in flagged},
        })
    # wall-clock digest from the optional perf side-channel (exports
    # written with include_perf=True): mean per-stage milliseconds
    # across rounds and validators — diagnostic only, not seeded
    samples: Dict[str, list] = {}
    for entry in tel.get("perf") or []:
        for per_stage in (entry.get("stage_ms") or {}).values():
            for stage, ms in per_stage.items():
                samples.setdefault(stage, []).append(ms)
    if samples:
        base["mean_stage_ms"] = {
            stage: sum(vals) / len(vals)
            for stage, vals in sorted(samples.items())}
    return base
