"""The (T, k) router and the grouped expert layer: selection against a
hand-written top-4, the grouped path against the one-hot einsum form
under routing so uneven that one expert gets most tokens and another
none, and no dropped token at any load."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipegoose_tpu.models.mixtral import _swiglu_experts
from pipegoose_tpu.nn.expert_parallel import (
    RouterOutput,
    SigmoidTopKRouter,
    TopKRouting,
    grouped_experts,
    moe_layer,
)

T, H, F, E, K = 48, 16, 24, 8, 4


def _router_params(bias_scale=0.0, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"gate": {"kernel": jax.random.normal(k1, (H, E)) * 0.5},
            "bias": jax.random.normal(k2, (E,)) * bias_scale}


def _tokens(seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (T, H))


def _experts(n=E, seed=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shapes = {"gate": (n, H, F), "up": (n, H, F), "down": (n, F, H)}
    return {name: {"kernel": jax.random.normal(k, shapes[name]) * 0.3}
            for name, k in zip(shapes, ks)}


def test_router_matches_a_hand_written_top4():
    router = SigmoidTopKRouter(E, K, scaling=1.8)
    params, x = _router_params(0.3), _tokens()
    out = router(params, x)
    s = 1.0 / (1.0 + np.exp(-np.asarray(x @ params["gate"]["kernel"])))
    choice = s + np.asarray(params["bias"])
    for t in range(T):
        want = np.argsort(-choice[t])[:K]
        assert set(np.asarray(out.experts[t])) == set(want)
        picked = s[t, np.asarray(out.experts[t])]
        np.testing.assert_allclose(
            np.asarray(out.weights[t]), picked / picked.sum() * 1.8,
            rtol=1e-5)
    assert out.experts.shape == (T, K) and out.weights.shape == (T, K)


def test_selection_moves_with_the_bias_and_weights_do_not():
    router = SigmoidTopKRouter(E, K, scaling=1.8, normalize=False)
    params, x = _router_params(0.0), _tokens()
    plain = router(params, x)
    # a bias that lifts expert 5 into every token's choice
    lifted = router(dict(params, bias=jnp.zeros(E).at[5].set(10.0)), x)
    assert bool((lifted.experts == 5).any(axis=1).all())
    assert not bool((plain.experts == 5).any(axis=1).all())
    # the weight of a chosen expert is its score alone, bias or not
    np.testing.assert_allclose(
        np.asarray(jnp.take_along_axis(lifted.scores, lifted.experts, 1)),
        np.asarray(lifted.weights) / 1.8, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(plain.scores),
                                  np.asarray(lifted.scores))


def test_the_bias_gets_no_gradient():
    router = SigmoidTopKRouter(E, K, scaling=1.8)
    grads = jax.grad(lambda p: router(p, _tokens()).weights.sum() ** 2)(
        _router_params(0.3))
    assert float(jnp.abs(grads["bias"]).max()) == 0.0
    assert float(jnp.abs(grads["gate"]["kernel"]).max()) > 0.0


def _one_hot_form(routing: TopKRouting) -> RouterOutput:
    """The picks as the einsum form wants them: (T, E, C) with C = T,
    so nothing is dropped."""
    experts = np.asarray(routing.experts)
    weights = np.asarray(routing.weights)
    dispatch = np.zeros((T, E, T), np.float32)
    combine = np.zeros((T, E, T), np.float32)
    used = np.zeros(E, int)
    for t in range(T):
        for j in range(K):
            e = experts[t, j]
            dispatch[t, e, used[e]] = 1.0
            combine[t, e, used[e]] = weights[t, j]
            used[e] += 1
    zero = jnp.zeros(())
    return RouterOutput(jnp.asarray(dispatch), jnp.asarray(combine), zero,
                        zero)


def _uneven_routing():
    """Expert 0 is every token's first pick, expert 3 is nobody's."""
    rng = np.random.RandomState(0)
    picks = np.stack([
        np.concatenate([[0], rng.choice([1, 2, 4, 5, 6, 7], K - 1,
                                        replace=False)])
        for _ in range(T)]).astype(np.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (T, K)), jnp.float32)
    return TopKRouting(jnp.asarray(picks), weights, jnp.zeros((T, E)))


def test_grouped_path_equals_the_einsum_form_under_uneven_routing():
    routing, x, ep = _uneven_routing(), _tokens(), _experts()
    y, rows = jax.jit(lambda ep, x: grouped_experts(ep, x, routing, (0, E)))(
        ep, x)
    assert int(rows[0]) == T and int(rows[3]) == 0
    assert int(rows.sum()) == T * K             # no pick was dropped
    mixtral_form = {"w1": ep["gate"], "w3": ep["up"], "w2": ep["down"]}
    want = moe_layer(mixtral_form, x, _one_hot_form(routing), None,
                     act=None, mlp_fn=_swiglu_experts)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)

    def both(fn):
        return jax.grad(lambda ep, x: (fn(ep, x) ** 2).sum(), argnums=(0, 1))

    got = both(lambda ep, x: grouped_experts(ep, x, routing, (0, E))[0])(ep, x)
    ref = both(lambda ep, x: moe_layer(
        {"w1": ep["gate"], "w3": ep["up"], "w2": ep["down"]}, x,
        _one_hot_form(routing), None, act=None, mlp_fn=_swiglu_experts))(ep, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


@pytest.mark.parametrize("held", [(0, 4), (4, 4), (2, 3)])
def test_a_share_computes_only_the_picks_on_its_experts(held):
    """Told which experts it holds, the layer gives their part of the
    sum and counts their rows; the rest is left out."""
    first, count = held
    routing, x, ep = _uneven_routing(), _tokens(), _experts()
    part = jax.tree_util.tree_map(lambda a: a[first:first + count], ep)
    y, rows = grouped_experts(part, x, routing, held)
    want = jnp.zeros((T, H))
    for e in range(first, first + count):
        w = jnp.where(routing.experts == e, routing.weights, 0.0).sum(-1)
        hid = jax.nn.silu(x @ ep["gate"]["kernel"][e]) * (
            x @ ep["up"]["kernel"][e])
        want = want + w[:, None] * (hid @ ep["down"]["kernel"][e])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    picks = np.asarray(routing.experts)
    np.testing.assert_array_equal(
        np.asarray(rows),
        [(picks == e).sum() for e in range(first, first + count)])


def test_no_tensor_of_the_layer_grows_with_tokens_times_experts():
    """The compiled layer holds no array with tokens x experts x
    anything: the largest is (T * k, width)."""
    t, e = 512, 64
    x = jnp.zeros((t, H))
    ep = jax.tree_util.tree_map(lambda a: jnp.zeros((8,) + a.shape[1:]),
                                _experts())
    params = {"gate": {"kernel": jnp.zeros((H, e))}, "bias": jnp.zeros(e)}
    router = SigmoidTopKRouter(e, K)

    def layer(x, ep, params):
        return grouped_experts(ep, x, router(params, x), (0, 8))[0]

    jaxpr = jax.make_jaxpr(layer)(x, ep, params)
    biggest = max(int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
                  for v in eqn.outvars)
    assert biggest <= t * K * max(H, F, e // K)
    assert biggest < t * e * K
