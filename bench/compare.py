"""The comparison that decides ``correct`` for a training cell.

Readings (from the program, the reference or the control) are
``{"losses": [per step], "ef1": {tensor: norm}, "change": {tensor:
norm}, "ef1_vec": {tensor: array}, "change_vec": {tensor: array}}``,
and the reference's also carry ``"grad1"`` (norms). The numbers against
the reference's:

  loss_gap              largest relative gap of a step's loss
  ef1_gap               worst tensor's gap between the norms of the
                        error feedback after the first step (the first
                        gradient less what that step sent)
  ef1_median_gap        the median tensor's gap of the same
  ef1_cos_gap           worst tensor's 1 - cos between the program's and
                        the reference's error feedback after step one
  ef1_cos_median        the median tensor's 1 - cos of the same
  change_gap            worst tensor's gap between the norms of the
                        parameters' change over the checked steps
  change_median_gap     the median tensor's gap of the same
  change_cos_gap        worst tensor's 1 - cos between the program's and
                        the reference's change of the parameters
  change_cos_median     the median tensor's 1 - cos of the same

A cell compares those its limits file (``bench/checks/<cell>.json``)
names. A tensor's norm gap is |program norm - reference norm| over the
larger of the reference's norm of that tensor and the reference's
median tensor norm, since some gradients are all but zero. The norms
cannot see a direction: a sign update moves every tensor by about
lr * sqrt(size) whichever way it points, so the cosines are what catch
an update applied the wrong way or coarser arithmetic. Tensors whose
reference first gradient is under ``EXCLUDE_FRAC`` of the median
tensor's move by round-off alone under a sign update and are left out
of the change.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EXCLUDE_FRAC = 1e-3
NUMBERS = ("loss_gap", "ef1_gap", "ef1_median_gap", "ef1_cos_gap",
           "ef1_cos_median", "change_gap", "change_median_gap",
           "change_cos_gap", "change_cos_median")


def _leaf_gaps(got: Dict[str, float], ref: Dict[str, float],
               names: List[str]) -> Dict[str, float]:
    if set(got) != set(ref):
        raise ValueError(f"tensors differ: program has "
                         f"{sorted(set(got) ^ set(ref))[:6]} apart")
    median = float(np.median([ref[n] for n in names]))
    return {n: abs(got[n] - ref[n]) / max(ref[n], median) for n in names}


@jax.jit
def _cos_terms(a, b):
    """(|a|, |b|, |a/|a| - b/|b||^2 / 2): the last is 1 - cos(a, b),
    taken as half the squared distance of the unit vectors so that it
    keeps its digits where the two nearly agree."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    na, nb = jnp.sqrt(jnp.sum(a * a)), jnp.sqrt(jnp.sum(b * b))
    d = a / jnp.where(na > 0, na, 1.0) - b / jnp.where(nb > 0, nb, 1.0)
    return na, nb, 0.5 * jnp.sum(d * d)


def one_minus_cos(a, b) -> float:
    """1 - cos between two tensors of one shape; 1 where exactly one of
    them is zero (orthogonal), 0 where both are."""
    if a.shape != b.shape:
        raise ValueError(f"shapes differ: {a.shape} and {b.shape}")
    na, nb, d = (float(x) for x in _cos_terms(jnp.asarray(a),
                                              jnp.asarray(b)))
    if not (math.isfinite(na) and math.isfinite(nb) and math.isfinite(d)):
        return math.inf
    if na == 0 or nb == 0:
        return 0.0 if na == nb else 1.0
    return d


def _cos_gaps(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              names: List[str]) -> Dict[str, float]:
    if set(got) != set(ref):
        raise ValueError(f"tensors differ: program has "
                         f"{sorted(set(got) ^ set(ref))[:6]} apart")
    return {n: one_minus_cos(got[n], ref[n]) for n in names}


def _worst(gaps: Dict[str, float]) -> Tuple[float, str]:
    for n, g in gaps.items():
        if not math.isfinite(g):
            return math.inf, n
    at = max(gaps, key=gaps.get)
    return gaps[at], at


def _median(gaps: Dict[str, float]) -> Tuple[float, str]:
    vals = list(gaps.values())
    if not all(math.isfinite(g) for g in vals):
        return math.inf, "non-finite"
    return float(np.median(vals)), f"median of {len(vals)}"


def counted(ref: dict) -> List[str]:
    """Tensors the change is compared on (see the module docstring)."""
    g = ref["grad1"]
    floor = EXCLUDE_FRAC * float(np.median(list(g.values())))
    return sorted(n for n, v in g.items() if v >= floor)


def gaps(got: dict, ref: dict) -> Dict[str, Tuple[float, str]]:
    """``{number: (gap, where)}`` for readings ``got`` against ``ref``."""
    if len(got["losses"]) != len(ref["losses"]):
        raise ValueError("different numbers of checked steps")
    loss = (0.0, "")
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"])):
        gap = abs(a - b) / abs(b)
        if not math.isfinite(gap):
            loss = (math.inf, f"step {i}")
            break
        loss = max(loss, (gap, f"step {i}"))
    moved = counted(ref)
    ef1 = _leaf_gaps(got["ef1"], ref["ef1"], sorted(ref["ef1"]))
    ef1_cos = _cos_gaps(got["ef1_vec"], ref["ef1_vec"], sorted(ref["ef1"]))
    change = _leaf_gaps(got["change"], ref["change"], moved)
    change_cos = _cos_gaps(got["change_vec"], ref["change_vec"], moved)
    return {"loss_gap": loss,
            "ef1_gap": _worst(ef1), "ef1_median_gap": _median(ef1),
            "ef1_cos_gap": _worst(ef1_cos),
            "ef1_cos_median": _median(ef1_cos),
            "change_gap": _worst(change),
            "change_median_gap": _median(change),
            "change_cos_gap": _worst(change_cos),
            "change_cos_median": _median(change_cos)}


def checks(g: Dict[str, Tuple[float, str]], limits: dict) -> List[tuple]:
    """``[(name, value, limit)]`` from ``gaps`` ``g`` for the numbers
    the cell's limits file names, in the order the result prints them."""
    return [(name, g[name][0], float(limits[name])) for name in g
            if name in limits]


def norms_only(readings: dict) -> dict:
    """The readings without their tensors, for a log line."""
    return {k: v for k, v in readings.items() if not k.endswith("_vec")}
