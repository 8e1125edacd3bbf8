"""An expert layer that is told which experts it holds (``MoEConfig.n_held_experts`` /
``first_held_expert``) and a LatentMoE (``latent_dim``): the guide's share test, the
guard round the rows that belong to no held expert, the a2a body over a held range, and
the held-rows counter."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.moe import experts as experts_mod
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.experts import sort_held_rows
from automodel_tpu.moe.layers import _shared_experts_forward, init_moe_params, moe_forward
from automodel_tpu.moe.metrics import held_rows_share

D, LATENT, E, K = 32, 16, 8, 3


def _full(**kw):
    base = dict(n_routed_experts=E, n_activated_experts=K, dim=D, moe_inter_dim=24,
                n_shared_experts=1, score_func="sigmoid", route_scale=5.0, norm_topk_prob=True,
                expert_activation="relu2", shared_expert_activation="relu2",
                shared_expert_inter_dim=40, force_score_correction_bias=True, latent_dim=LATENT)
    base.update(kw)
    return MoEConfig(**base)


def _share(cfg, params, first, n):
    """The configuration and parameters of the chip that holds experts first..first+n."""
    held = dataclasses.replace(cfg, n_held_experts=n, first_held_expert=first, n_shared_experts=0)
    part = {k: v for k, v in params.items() if k != "shared_experts"}
    part["experts"] = jax.tree.map(lambda a: a[first:first + n], params["experts"])
    return held, part


@pytest.fixture(scope="module")
def layer():
    with jax.default_matmul_precision("highest"):
        cfg = _full()
        params = init_moe_params(cfg, jax.random.key(0), jnp.float32, 0.2)
        x = jax.random.normal(jax.random.key(1), (2, 10, D))
        yield cfg, params, x


@pytest.mark.parametrize("n_held", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(layer, n_held):
    """What every share gives for its own experts (the latent up-projection applied to each
    part), plus the shared expert and nothing else counted once, is the whole layer."""
    cfg, params, x = layer
    whole, _, load = moe_forward(cfg, params, x)
    total = _shared_experts_forward(cfg, params, x.reshape(-1, D)).reshape(x.shape)
    for first in range(0, E, n_held):
        held, part = _share(cfg, params, first, n_held)
        y, _, part_load = moe_forward(held, part, x)
        np.testing.assert_array_equal(part_load, load)  # the router scores all E everywhere
        total = total + y
    np.testing.assert_allclose(total, whole, atol=2e-5)


def test_a_share_holds_only_its_experts_parameters():
    held = _full(n_held_experts=2, first_held_expert=4)
    shapes = jax.eval_shape(lambda k: init_moe_params(held, k), jax.random.key(0))
    assert shapes["experts"]["gate_up_proj"].shape == (2, LATENT, 24)
    assert shapes["experts"]["down_proj"].shape == (2, 24, LATENT)
    assert shapes["gate"]["weight"].shape == (E, D)
    assert shapes["gate"]["score_correction_bias"].shape == (E,)
    assert shapes["latent"]["w_down"].shape == (D, LATENT)
    assert shapes["latent"]["w_up"].shape == (LATENT, D)
    with pytest.raises(ValueError, match="lie outside"):
        _full(n_held_experts=4, first_held_expert=6)


def test_a_share_gathers_no_more_rows_than_can_land_on_it(layer):
    """The static bound: a token picks distinct experts, so at most min(K, held) of its
    pairs are held here; the GEMMs' groups count the pairs that came."""
    cfg, params, x = layer
    held, part = _share(cfg, params, 2, 2)
    seen = {}
    real = experts_mod.sorted_ragged_ffn

    def spy(cfg_, p, xs, ids, group_sizes, **kw):
        seen["rows"], seen["groups"] = xs.shape[0], group_sizes
        return real(cfg_, p, xs, ids, group_sizes, **kw)

    experts_mod.sorted_ragged_ffn, keep = spy, experts_mod.sorted_ragged_ffn
    try:
        _, _, load = moe_forward(held, part, x)
    finally:
        experts_mod.sorted_ragged_ffn = keep
    tokens = x.shape[0] * x.shape[1]
    assert seen["rows"] == tokens * 2  # not tokens * K
    np.testing.assert_array_equal(seen["groups"], np.asarray(load[2:4], np.int32))


def test_rows_behind_the_groups_reach_neither_the_result_nor_a_gradient(layer, monkeypatch):
    """A grouped GEMM may leave anything in the rows that belong to no group (the TPU's
    does not promise zeros). Poisoned there, forward and backward, nothing changes."""
    cfg, params, x = layer
    held, part = _share(cfg, params, 2, 2)

    def loss(p, x):
        return jnp.sum(jnp.sin(moe_forward(held, p, x)[0]))

    want = jax.value_and_grad(loss, argnums=(0, 1))(part, x)

    @jax.custom_vjp
    def poisoned(xs, w, group_sizes):
        out = jax.lax.ragged_dot(xs, w, group_sizes)
        return jnp.where((jnp.arange(xs.shape[0]) < group_sizes.sum())[:, None], out, jnp.nan)

    def fwd(xs, w, group_sizes):
        return poisoned(xs, w, group_sizes), (xs, w, group_sizes)

    def bwd(res, g):
        xs, w, group_sizes = res
        _, pull = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, group_sizes), xs, w)
        dxs, dw = pull(g)
        behind = (jnp.arange(xs.shape[0]) >= group_sizes.sum())[:, None]
        return jnp.where(behind, jnp.nan, dxs), dw, None

    poisoned.defvjp(fwd, bwd)
    monkeypatch.setattr(experts_mod, "_expert_gemm", lambda xs, w, gs, backend: poisoned(xs, w, gs))
    got = jax.value_and_grad(loss, argnums=(0, 1))(part, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_sort_held_rows_orders_held_rows_first_and_counts_them_alone():
    ids = jnp.asarray([5, 0, -1, 1, 0, 9, 1, 1])
    order, sorted_ids, groups, n_rows = sort_held_rows(ids, 2, bound=6)
    assert int(n_rows) == 5 and groups.tolist() == [2, 3]
    assert order[:5].tolist() == [1, 4, 3, 6, 7] and sorted_ids[:5].tolist() == [0, 0, 1, 1, 1]
    assert sorted_ids.max() < 2  # rows behind the groups still index a held expert
    order, sorted_ids, groups, n_rows = sort_held_rows(jnp.asarray([1, 0, 1]), 2)
    assert n_rows is None and order.tolist() == [1, 0, 2] and groups.tolist() == [1, 2]


def test_the_a2a_body_over_a_held_range_is_the_held_layer(layer):
    """ep = 1 inside a manual region: the a2a body (exchange with itself) of a layer that
    holds experts 2..5 gives what the one-chip share gives, drops nothing, and counts a
    pair routed to an expert held nowhere on the mesh neither as sent nor as dropped."""
    from jax.sharding import Mesh, PartitionSpec as P

    from automodel_tpu.moe.dispatch import make_ep_dispatch_body

    cfg, params, x = layer
    held, part = _share(cfg, params, 2, 4)
    held = dataclasses.replace(held, n_shared_experts=1)
    part["shared_experts"] = params["shared_experts"]
    want, _, _ = moe_forward(held, part, x)
    body = make_ep_dispatch_body(held, 1, capacity=x.shape[0] * x.shape[1] * K)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("ep",))
    fn = jax.shard_map(lambda p, x: body(p, x, jnp.ones(x.shape[:2], bool)), mesh=mesh,
                       in_specs=(P(), P()), out_specs=(P(), None, P(), P()), check_vma=False)
    got, _, _, dropped = fn(part, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(dropped) == 0.0


def test_held_rows_share_counts_pairs_routed_to_held_experts():
    loads = np.asarray([[4, 0, 2, 2], [1, 1, 1, 5]], np.float32)  # two layers, four experts
    assert held_rows_share(loads, 0, 4) == 1.0
    assert held_rows_share(loads, 1, 2) == pytest.approx((0 + 2 + 1 + 1) / 16)
    assert held_rows_share(np.zeros((2, 4)), 1, 2) == 0.0


def test_the_capacity_dispatch_refuses_a_share(layer):
    cfg, params, x = layer
    held, part = _share(cfg, params, 0, 2)
    with pytest.raises(ValueError, match="share"):
        moe_forward(held, part, x, dispatcher="capacity")


def test_routing_that_crowds_the_held_experts_is_not_dropped():
    """Every token sent to both held experts (the worst case the static size is for): the
    shares still add up to the uncut layer."""
    with jax.default_matmul_precision("highest"):
        cfg = _full()
        params = init_moe_params(cfg, jax.random.key(0), jnp.float32, 0.2)
        bias = jnp.zeros((E,)).at[2:4].set(10.0)
        params["gate"] = dict(params["gate"], score_correction_bias=bias)
        x = jax.random.normal(jax.random.key(2), (1, 64, D))
        whole, _, load = moe_forward(cfg, params, x)
        assert float(load[2]) == float(load[3]) == 64
        total = _shared_experts_forward(cfg, params, x.reshape(-1, D)).reshape(x.shape)
        for first in range(0, E, 2):
            held, part = _share(cfg, params, first, 2)
            total = total + moe_forward(held, part, x)[0]
    np.testing.assert_allclose(total, whole, atol=5e-5)


@pytest.mark.parametrize("crowded", [False, True], ids=["routing_as_drawn", "every_token_on_both"])
def test_a_share_and_its_gradients_are_the_uncut_layer_with_the_absent_experts_silenced(crowded):
    """512 tokens, experts 2 and 3 held. The uncut layer (every expert here, no static
    bound, no rows behind the groups) with the other experts' down-projections at zero
    computes the same function of x, the router, the latent maps and the two experts;
    so do its gradients, also where every token lands on both held experts and the
    gather is full."""
    with jax.default_matmul_precision("highest"):
        cfg = _full(n_shared_experts=0)
        params = init_moe_params(cfg, jax.random.key(0), jnp.float32, 0.2)
        x = jax.random.normal(jax.random.key(2), (1, 512, D))
        if crowded:
            bias = jnp.zeros((E,)).at[2:4].set(10.0)
            params["gate"] = dict(params["gate"], score_correction_bias=bias)
        held, part = _share(cfg, params, 2, 2)
        absent = (jnp.arange(E) < 2) | (jnp.arange(E) >= 4)
        silenced = dict(params, experts=dict(
            params["experts"],
            down_proj=jnp.where(absent[:, None, None], 0, params["experts"]["down_proj"])))

        def loss(layer_cfg, p, x):
            y, _, load = moe_forward(layer_cfg, p, x)
            return jnp.sum(jnp.sin(y)), load

        grad = jax.value_and_grad(loss, argnums=(1, 2), has_aux=True)
        (got, load), (got_p, got_x) = grad(held, part, x)
        (want, _), (want_p, want_x) = grad(cfg, silenced, x)
    assert (float(load[2:4].sum()) == 2 * 512) == crowded
    want_p["experts"] = jax.tree.map(lambda a: a[2:4], want_p["experts"])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got_x, want_x, atol=2e-4, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(got_p), jax.tree.leaves(want_p)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-4)  # sums over 512 tokens
