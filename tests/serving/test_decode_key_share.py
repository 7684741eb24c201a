"""The walk's rate of engagement (PR 32): ``finish_run()`` reports
``decode_key_share``, the key columns the plain decode steps walked over
those their page tables reach. The host counts with the arithmetic the
device takes its trip count from (``kv_pool.walked_chunks``) on the
lengths it sends (tests/serving/test_kv_pool.py holds the device to that
arithmetic: a page of a chunk past it is never read)."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipegoose_tpu.models import bloom, generate as gen
from pipegoose_tpu.serving import Request, ServingEngine, kv_pool
from pipegoose_tpu.telemetry import MetricsRegistry

MIXED = [(3, 5), (9, 12), (17, 4), (5, 9), (12, 7), (2, 15)]
PS, CONTEXT, WALK = 4, 64, 8             # 16 table entries, 8 chunks of 8


@pytest.fixture(scope="module")
def setup():
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2, n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    return cfg, params, [rng.randint(1, 64, (s,)) for s, _ in MIXED]


def test_one_arithmetic_for_host_and_device():
    """``walked_chunks`` is the same function of the same number on a
    Python int and on a traced value, a trip a chunk the position has
    reached; ``walk_plan`` cuts a table into whole pages."""
    traced = jax.jit(lambda p: kv_pool.walked_chunks(p, WALK))
    for pos in (0, 1, WALK - 1, WALK, WALK + 1, 5 * WALK - 1, CONTEXT - 1):
        want = pos // WALK + 1
        assert kv_pool.walked_chunks(pos, WALK) == want
        assert isinstance(kv_pool.walked_chunks(pos, WALK), int)
        assert int(traced(jnp.int32(pos))) == want
    assert kv_pool.WALK_KEYS == 256
    assert kv_pool.walk_plan(16, 128) == (16, 8)     # serve-chat-r8's table
    assert kv_pool.walk_plan(16, 5) == (5, 1)        # narrower than a chunk
    assert kv_pool.walk_plan(512, 4) == (1, 4)       # a page over a chunk
    assert kv_pool.walk_plan(4, 7)[1] * kv_pool.walk_plan(4, 7)[0] >= 7


def test_key_share_is_what_the_steps_were_sent(setup, monkeypatch):
    """A scripted run of mixed lengths on a clock of fixed quanta: the
    share ``finish_run()`` reports is the walk's arithmetic over the
    ``seq_lens`` each decode step ran on (sent by the host, or carried on
    the device from the step before), the gauge mirrors it, and the
    tokens are ``generate()``'s."""
    monkeypatch.setattr(kv_pool, "WALK_KEYS", WALK)
    cfg, params, prompts = setup
    reg = MetricsRegistry(enabled=True)
    eng = ServingEngine(params, cfg, num_slots=3, num_pages=32, page_size=PS,
                        max_context=CONTEXT, registry=reg)
    sent = []
    step = eng._step

    def spy(p, carry, kp, vp):
        # the lengths the step RAN on, out of its packed inputs, whether
        # the host sent them or the step before left them on the device.
        # A COPY, taken before the call: the step is handed them donated,
        # and on the CPU a device array may share a host buffer
        sent.append(np.array(eng._unpack_carry(np.asarray(carry))[1]))
        return step(p, carry, kp, vp)

    eng._step = spy
    clock = itertools.count()
    outs, metrics = eng.run(
        [Request(prompt=p, max_new_tokens=n)
         for p, (_, n) in zip(prompts, MIXED)],
        now=lambda: next(clock) * 1e-3)
    for o, p, (_, n) in zip(outs, prompts, MIXED):
        want = gen.generate(params, jnp.asarray(p)[None], cfg,
                            max_new_tokens=n)
        np.testing.assert_array_equal(o.generated,
                                      np.asarray(want)[0, len(p):])
    assert len(sent) == metrics["decode_steps"] > 0
    walked = sum(min((int(s.max()) // WALK + 1) * WALK, CONTEXT)
                 for s in sent)
    share = walked / (len(sent) * CONTEXT)
    # lengths of 3 to 23 in a table of 64: between one chunk and three
    assert WALK / CONTEXT < share < 3 * WALK / CONTEXT
    assert metrics["decode_key_share"] == pytest.approx(share, abs=1e-6)
    assert reg.snapshot()["gauges"]["serving.decode_key_share"] == \
        pytest.approx(share)


def test_key_share_of_an_idle_run(setup):
    """No decode step: 0.0, like the occupancies."""
    cfg, params, prompts = setup
    kw = dict(num_slots=2, num_pages=32, page_size=PS, max_context=CONTEXT)
    eng = ServingEngine(params, cfg, **kw)
    _, metrics = eng.run([Request(prompt=prompts[0], max_new_tokens=1)])
    assert metrics["decode_steps"] == 0
    assert metrics["decode_key_share"] == 0.0
