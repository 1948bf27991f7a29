"""The check that decides ``correct``, driven through the rest of a run
at test size on the CPU: a sound run passes, and each fault a training
cell on one chip can have, planted under the timed path, reads false."""
import pytest

import faults
import tinycell


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell.make_root(str(tmp_path_factory.mktemp("faults")))


def test_sound_run_is_correct(root):
    res = tinycell.run(root)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"peer_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [faults.stale_state, faults.half_batch,
                                   faults.negated_update],
                         ids=["state_unchanged", "half_batch",
                              "negated_update"])
def test_fault_is_not_correct(root, fault):
    res = tinycell.run(root, step_fault=fault)
    assert not res["correct"], res["checks"]
