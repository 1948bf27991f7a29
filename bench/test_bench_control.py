"""The low-precision control at test size: the reference computed with
float8 matrix products in the program's place fails the limits each
training cell holds, while the float32 reference agrees with itself.
This keeps the comparison able to see coarser arithmetic as the code
changes; that the control fails at each cell's own size is read on the
chip (``bench/calibrate.py``), and the limits are set from those
readings."""
import numpy as np
import pytest

import compare
import harness
import seeded
import tinycell


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tinycell.make_root(str(tmp_path_factory.mktemp("control")),
                              dtype="bfloat16")
    return harness.find_cell(tinycell.CELL, root)


def _readings(cell, mode, seed=5):
    ref = harness.reference(cell)
    t = cell.traffic
    first = seeded.token_batches(seed, t["pool"], t["batch"], t["seq"],
                                 cell.config["vocab_size"])[:3]
    return ref.train_readings(
        cell.config, harness.entry(cell).hyper(t),
        seeded.canonical_weights(cell.config, seed), first,
        mode=mode, stacked=False,
        initial=lambda: seeded.canonical_weights(cell.config, seed))


@pytest.fixture(scope="module")
def readings(cell):
    return {m: _readings(cell, m) for m in ("float32", "fp8")}


@pytest.mark.parametrize("limits_of", ["templar-1b.peer-accum16",
                                       "qwen2-1.5b.peer-accum16",
                                       "templar-1b.peer-step2k"])
def test_fp8_control_fails_the_limits(readings, limits_of):
    limits = harness._read_json(
        f"{tinycell.BENCH}/checks/{limits_of}.json")
    assert not all(harness.passed(v, lim) for _, v, lim in compare.checks(
        compare.gaps(readings["fp8"], readings["float32"]), limits))


def test_reference_agrees_with_itself(cell, readings):
    again = _readings(cell, "float32")
    assert all(g == 0.0 for g, _ in
               compare.gaps(again, readings["float32"]).values())


def test_one_minus_cos_keeps_its_digits():
    """1 - cos as the comparison takes it, against float64 arithmetic,
    where the two tensors nearly agree, point opposite ways, or one of
    them is zero."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 48)).astype(np.float32)
    b = (a + 1e-3 * rng.standard_normal(a.shape)).astype(np.float32)
    a64, b64 = a.astype(np.float64).ravel(), b.astype(np.float64).ravel()
    want = 1 - a64 @ b64 / np.linalg.norm(a64) / np.linalg.norm(b64)
    assert compare.one_minus_cos(a, b) == pytest.approx(want, rel=1e-3)
    assert compare.one_minus_cos(a, -a) == pytest.approx(2.0)
    assert compare.one_minus_cos(a, a) == 0.0
    assert compare.one_minus_cos(0 * a, a) == 1.0
    assert compare.one_minus_cos(0 * a, 0 * a) == 0.0
    with pytest.raises(ValueError):
        compare.one_minus_cos(a, a[:1])
