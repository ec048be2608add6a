"""A whole serving run on the CPU at a tiny size, and that `correct` comes
out false under the control and under a planted fault."""
import pytest

from bench.tests import util


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return util.tiny_bench(tmp_path_factory.mktemp("checkout"))


def test_serve_run_is_correct(bench):
    r = util.run_cell(bench, "tiny.chat", seed=2**33 + 5, seconds=3.0)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "ttft_p50_ms", "itl_p90_ms"}
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1


@pytest.mark.parametrize("hook", ["control", "token_altered"])
def test_serve_control_and_fault_are_not_correct(bench, hook):
    plant = util.control if hook == "control" else util.fault(hook)
    r = util.run_cell(bench, "tiny.chat", seed=11, seconds=3.0, hook=plant)
    assert not r["correct"], r["checks"]
