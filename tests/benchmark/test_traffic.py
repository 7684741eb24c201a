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
                           "bloom-560m.serve-chat-r8.json")) as f:
        w = json.load(f)
    return dict(w["traffic"], page_size=w["engine"]["page_size"])


def test_same_seed_same_requests():
    spec = chat_spec()
    a = traffic.plan(spec, 1000, 2 ** 31 + 5, 50)
    b = traffic.plan(spec, 1000, 2 ** 31 + 5, 50)
    assert [p.due_s for p in a] == [p.due_s for p in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_other_seed_same_work_in_another_order():
    spec = {k: v for k, v in chat_spec().items() if k != "order_seed"}
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


def test_the_chat_cell_plans_240_requests_of_the_same_lengths_every_seed():
    """8.0 requests/s over the benchmark's 30 s: 240 requests, the same
    multiset of prompt and output lengths for any two seeds, every one
    due inside the window."""
    spec = chat_spec()
    assert spec["rate_per_s"] == 8.0
    n = traffic.n_requests(spec, 30)
    assert n == 240
    a = traffic.plan(spec, 250880, 2 ** 31 + 77, n)
    b = traffic.plan(spec, 250880, 5, n)
    assert len(a) == len(b) == 240
    ps = spec["page_size"]
    for lengths in (lambda plan: sorted(-(-len(p.prompt) // ps) for p in plan),
                    lambda plan: sorted(p.new_tokens for p in plan)):
        assert lengths(a) == lengths(b)
    for plan in (a, b):
        assert plan[0].due_s == 0.0
        assert all(0.0 <= p.due_s < 30.0 for p in plan)
        # every request fits the engine: prompt + output <= max_context
        assert max(len(p.prompt) + p.new_tokens for p in plan) <= 2048


def test_an_order_seed_gives_every_seed_one_schedule_with_other_tokens():
    """With ``order_seed`` (the chat cell has one) every seed replays one
    schedule of arrivals and lengths and changes the token ids alone;
    without it, lengths and gaps are permuted afresh a seed."""
    spec = chat_spec()
    assert "order_seed" in spec
    n = 240

    def schedule(plan):
        return [(p.due_s, len(p.prompt), p.new_tokens) for p in plan]

    a, b = (traffic.plan(spec, 250880, seed, n) for seed in (3, 2 ** 31 + 9))
    assert schedule(a) == schedule(b)
    assert not any((x.prompt == y.prompt).all() for x, y in zip(a, b))
    free = {k: v for k, v in spec.items() if k != "order_seed"}
    c, d = (traffic.plan(free, 250880, seed, n) for seed in (3, 4))
    assert schedule(c) != schedule(d) != schedule(a)
    assert sorted(p.new_tokens for p in c) == sorted(p.new_tokens for p in a)
    # the schedule is the one its seed draws when nothing is fixed
    assert schedule(traffic.plan(free, 250880, spec["order_seed"], n)) \
        == schedule(a)


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
