"""A whole training run on the CPU at a tiny size, and that `correct`
comes out false under the control and under each planted fault."""
import pytest

from bench.tests import util


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return util.tiny_bench(tmp_path_factory.mktemp("checkout"))


def test_train_run_is_correct(bench):
    r = util.run_cell(bench, "tiny.train", seed=2**33 + 7)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 3 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "train_tokens_per_s"}


@pytest.mark.parametrize("hook", ["control", "half_batch", "frozen_state"])
def test_train_control_and_faults_are_not_correct(bench, hook):
    plant = util.control if hook == "control" else util.fault(hook)
    r = util.run_cell(bench, "tiny.train", seed=13, hook=plant)
    assert not r["correct"], r["checks"]
