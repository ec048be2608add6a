"""The open-loop generator: the same seed gives the same schedule; another
seed gives another order of the same work."""
import json

from bench import traffic
from bench.tests.util import BENCH

MIX = json.loads((BENCH / "traffic" / "chat-open.json").read_text())


def _key(items):
    return [(it.due_s, it.prompt, it.max_new) for it in items]


def test_same_seed_same_schedule():
    a = traffic.schedule(MIX, 2**40 + 3, 30.0, 151936)
    b = traffic.schedule(MIX, 2**40 + 3, 30.0, 151936)
    assert _key(a) == _key(b)


def test_other_seed_other_prompts_same_work():
    a = traffic.schedule(MIX, 1, 30.0, 151936)
    b = traffic.schedule(MIX, 2, 30.0, 151936)
    assert _key(a) != _key(b)
    assert [it.prompt for it in a] != [it.prompt for it in b]
    # the same sizes on the same schedule
    assert [it.due_s for it in a] == [it.due_s for it in b]
    assert [len(it.prompt) for it in a] == [len(it.prompt) for it in b]
    assert [it.max_new for it in a] == [it.max_new for it in b]


def test_schedule_shape():
    items = traffic.schedule(MIX, 5, 30.0, 151936)
    assert len(items) == traffic.n_requests(MIX, 30.0)
    due = [it.due_s for it in items]
    assert due == sorted(due) and 0.0 < due[0] and due[-1] < 30.0
    sys_len = MIX["system_prompt_len"]
    for it in items:
        assert MIX["prompt"]["lo"] <= len(it.prompt) <= MIX["prompt"]["hi"]
        assert MIX["output"]["lo"] <= it.max_new <= MIX["output"]["hi"]
        assert it.prompt[:sys_len] == items[0].prompt[:sys_len]
        assert all(0 <= t < 151936 for t in it.prompt)
    # sizes are the law's quantiles, so windows of 15 and of 31 requests
    # carry the same mean answer and prompt within a few percent
    means = [[sum(f(it) for it in s) / len(s) for s in (
        traffic.schedule(dict(MIX, rate_per_s=r), 5, 51.0, 151936)
        for r in (0.3, 0.6))] for f in (lambda it: it.max_new,
                                        lambda it: len(it.prompt))]
    for a, b in means:
        assert abs(a - b) < 0.1 * b


def test_batches_repeat_and_rows_differ():
    t1, l1 = traffic.batch_tokens(2**35, 4, 4, 64, 1000, 1.2)
    t2, l2 = traffic.batch_tokens(2**35, 4, 4, 64, 1000, 1.2)
    assert (t1 == t2).all() and (l1 == l2).all()
    assert (t1[:, 1:] == l1[:, :-1]).all()
    assert len({tuple(r) for r in t1}) == 4
    t3, _ = traffic.batch_tokens(2**35, 5, 4, 64, 1000, 1.2)
    assert (t1 != t3).any()


def test_zipf_len_copy():
    import numpy as np
    rng = np.random.RandomState(0)
    xs = [traffic.zipf_len(rng, 1.8, 2, 12) for _ in range(500)]
    assert min(xs) == 2 and max(xs) == 12
