"""The traffic generator: the seed changes order and content, never the
amount of work."""
import json
import os

import numpy as np

from benchmark import traffic

HERE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark")


def chat_spec():
    with open(os.path.join(HERE, "workloads",
                           "bloom-560m.serve-chat.json")) as f:
        w = json.load(f)
    return dict(w["traffic"], page_size=w["engine"]["page_size"])


def test_same_seed_same_requests():
    spec = chat_spec()
    a = traffic.plan(spec, 1000, 2 ** 31 + 5, 50)
    b = traffic.plan(spec, 1000, 2 ** 31 + 5, 50)
    assert [p.due_s for p in a] == [p.due_s for p in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_other_seed_same_work_in_another_order():
    spec = chat_spec()
    a = traffic.plan(spec, 1000, 1, 120)
    b = traffic.plan(spec, 1000, 2, 120)
    ps = spec["page_size"]
    pages = lambda plan: sorted(-(-len(p.prompt) // ps) for p in plan)  # noqa: E731
    assert pages(a) == pages(b)
    assert sorted(p.new_tokens for p in a) == sorted(p.new_tokens for p in b)
    gaps = lambda plan: np.sort(np.diff([p.due_s for p in plan]))  # noqa: E731
    np.testing.assert_allclose(gaps(a)[:-1], gaps(b)[:-1], rtol=1e-9, atol=0.2)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]


def test_every_prompt_lands_on_a_declared_bucket():
    spec = chat_spec()
    ps = spec["page_size"]
    allowed = {b // ps for b in spec["prompt_buckets"]}
    plan = traffic.plan(spec, 250880, 7, 400)
    assert {-(-len(p.prompt) // ps) for p in plan} <= allowed
    assert all(1 <= p.prompt.min() and p.prompt.max() < 250880 for p in plan)
    lo, hi = spec["output"]["min"], spec["output"]["max"]
    assert all(lo <= p.new_tokens <= hi for p in plan)


def test_open_loop_arrivals_fill_the_window_at_the_declared_rate():
    spec = chat_spec()
    n = traffic.n_requests(spec, 30)
    assert n == int(spec["rate_per_s"] * 30)
    due = [p.due_s for p in traffic.plan(spec, 1000, 3, n)]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 30


def test_lognormal_lengths_sit_at_the_declared_median_and_limits():
    vals = traffic.draw_lengths({"dist": "lognormal", "median": 192,
                                 "sigma": 0.9, "min": 32, "max": 1024}, 999)
    assert vals.min() >= 32 and vals.max() <= 1024
    assert abs(int(np.median(vals)) - 192) <= 1


def test_training_rows_all_differ():
    a = traffic.token_batch(250880, 9, 0, 8, 64)
    b = traffic.token_batch(250880, 9, 1, 8, 64)
    assert a.shape == (8, 64) and a.dtype == np.int32
    assert len({r.tobytes() for r in np.concatenate([a, b])}) == 16
    assert (a == traffic.token_batch(250880, 9, 0, 8, 64)).all()
