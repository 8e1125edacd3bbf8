"""An expert layer that is told which experts it holds (``MoEConfig.n_held_experts`` /
``first_held_expert``) and a LatentMoE (``latent_dim``): the guide's share test, the
block loop that runs as far as rows came, the guard round the rows that belong to no
held expert, the a2a body over a held range, and the held-rows counters."""

import dataclasses
import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.moe import experts as experts_mod
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.experts import (
    HELD_BLOCK_ROWS as B,
    grouped_experts_apply,
    init_expert_params,
    sort_held_rows,
)
from automodel_tpu.moe.layers import _shared_experts_forward, init_moe_params, moe_forward
from automodel_tpu.moe.metrics import held_row_blocks_share, held_rows_share

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


def _routing(tokens, per_expert):
    """(weights, indices) of ``tokens`` tokens, K picks each among E experts, that send
    exactly ``per_expert[j]`` tokens (the first ones) to expert ``2 + j``, one slot an
    expert, and every other pick to experts that are not held (0, 1, 5)."""
    spare = np.asarray([0, 1, 5])
    indices = np.tile(spare, (tokens, 1))
    for slot, n in enumerate(per_expert):
        indices[:n, slot] = 2 + slot
    weights = jax.random.uniform(jax.random.key(7), (tokens, K), minval=0.5, maxval=1.5)
    return weights, jnp.asarray(indices, jnp.int32)


# experts 2, 3, 4 held (as many as a token picks: the crowded case can fill the ceiling)
_ROW_COUNTS = {
    "no_rows": (0, 0, 0),
    "one_row": (1, 0, 0),
    "a_block_less_one": (B // 2, B // 2 - 1, 0),
    "a_block": (B // 2, B // 2, 0),
    "a_block_and_one": (B // 2 + 1, B // 2, 0),
    "an_expert_straddles_two_blocks": (B - 100, 300, 40),  # expert 3: rows B-100 .. B+200
    "every_pick_held": (B + 152, B + 152, B + 152),  # the ceiling: every block runs
}
_TOKENS = B + 152


@pytest.mark.parametrize("per_expert", _ROW_COUNTS.values(), ids=_ROW_COUNTS.keys())
def test_a_share_gathers_no_more_rows_than_can_land_on_it(per_expert):
    """The loop's body sees one block of B rows whatever came, and runs once for every
    block that holds a row: ``ceil(n_rows / B)`` times, none for none, and to the static
    ceiling ``ceil(T x min(K, held) / B)`` only when every pick is held. The blocks'
    groups are the held experts' rows cut at the block edges: they add up to the load."""
    cfg = _full(n_held_experts=3, first_held_expert=2, n_shared_experts=0)
    params = init_expert_params(cfg, jax.random.key(0), jnp.float32, 0.2)
    x = jax.random.normal(jax.random.key(1), (_TOKENS, LATENT))
    weights, indices = _routing(_TOKENS, per_expert)
    heights, blocks = [], []
    real = experts_mod.sorted_ragged_ffn

    def spy(cfg_, p, xs, ids, group_sizes, **kw):
        heights.append(xs.shape[0])
        jax.debug.callback(lambda g: blocks.append(np.asarray(g)), group_sizes, ordered=True)
        return real(cfg_, p, xs, ids, group_sizes, **kw)

    experts_mod.sorted_ragged_ffn, keep = spy, experts_mod.sorted_ragged_ffn
    try:
        jax.block_until_ready(grouped_experts_apply(cfg, params, x, weights, indices))
        jax.effects_barrier()
    finally:
        experts_mod.sorted_ragged_ffn = keep
    assert heights == [B]  # traced once, at the block's height: not tokens * K, not the ceiling
    assert len(blocks) == -(-sum(per_expert) // B)
    assert all(g.sum() == B for g in blocks[:-1])  # only the last block is part empty
    np.testing.assert_array_equal(np.sum(blocks, axis=0) if blocks else np.zeros(3), per_expert)
    if sum(per_expert) == _TOKENS * K:
        assert len(blocks) == -(-_TOKENS * min(K, 3) // B)


@pytest.mark.parametrize("per_expert", _ROW_COUNTS.values(), ids=_ROW_COUNTS.keys())
def test_the_block_loop_and_its_gradients_are_the_uncut_experts_with_the_absent_silenced(per_expert):
    """The share's loop and its own backward against the straight-line code of a layer
    that holds every expert, the experts that are not here silenced: the value, and the
    gradients of x, of the weights and of both expert parameters, at every row count
    that meets a block's edge."""
    with jax.default_matmul_precision("highest"):
        cfg = _full(n_shared_experts=0)
        held = dataclasses.replace(cfg, n_held_experts=3, first_held_expert=2)
        params = init_expert_params(cfg, jax.random.key(0), jnp.float32, 0.2)
        absent = (jnp.arange(E) < 2) | (jnp.arange(E) >= 5)
        silenced = dict(params, down_proj=jnp.where(absent[:, None, None], 0, params["down_proj"]))
        x = jax.random.normal(jax.random.key(1), (_TOKENS, LATENT))
        weights, indices = _routing(_TOKENS, per_expert)

        def grad(layer_cfg, p):
            def loss(p, x, w):
                return jnp.sum(jnp.sin(grouped_experts_apply(layer_cfg, p, x, w, indices)))

            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(p, x, weights)

        got, (got_p, got_x, got_w) = grad(held, jax.tree.map(lambda a: a[2:5], params))
        want, (want_p, want_x, want_w) = grad(cfg, silenced)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_x, want_x, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(got_w, want_w, atol=2e-5, rtol=1e-4)
    for name in ("gate_up_proj", "down_proj"):  # sums over up to B + 152 rows an expert
        np.testing.assert_allclose(got_p[name], want_p[name][2:5], atol=5e-4, rtol=1e-4)
    if sum(per_expert) == 0:
        assert not np.any(got_x) and not np.any(got_w) and not np.any(got_p["down_proj"])


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


def test_held_row_blocks_share_counts_the_blocks_the_loop_ran():
    """Blocks run over the most any routing of the same tokens runs: four experts, two
    picks a token, experts 1 and 2 held, so a layer's ceiling is ceil(tokens x 2 / B)."""
    tokens = 3 * B  # 6 B pairs a layer, at most 6 B of them held: six blocks
    even = np.full((2, 4), tokens * 2 / 4)  # 3 B held rows a layer: three blocks of six
    assert held_row_blocks_share(even, 1, 2, top_k=2) == pytest.approx(3 / 6)
    crowded = np.asarray([[0, tokens, tokens, 0]] * 2)  # every pick held: the loop's ceiling
    assert held_row_blocks_share(crowded, 1, 2, top_k=2) == 1.0
    none = np.asarray([[tokens, 0, 0, tokens]] * 2)
    assert held_row_blocks_share(none, 1, 2, top_k=2) == 0.0
    one_row = np.asarray([[tokens, 1, 0, tokens - 1], [tokens, 0, 0, tokens]])
    assert held_row_blocks_share(one_row, 1, 2, top_k=2) == pytest.approx(1 / 12)  # a layer rounds up alone
    assert held_row_blocks_share(np.zeros((2, 4)), 1, 2, top_k=2) == 0.0


def _qwen_experts_jaxpr(cfg_kw=None):
    """``grouped_experts_apply`` at the Qwen cell's shapes (8192 tokens, 2048 wide, 8 of 128
    experts of width 768), traced on shapes alone."""
    cfg = MoEConfig(n_routed_experts=128, n_activated_experts=8, dim=2048, moe_inter_dim=768,
                    norm_topk_prob=True, **(cfg_kw or {}))
    params = jax.eval_shape(lambda k: init_expert_params(cfg, k, jnp.bfloat16), jax.random.key(0))
    shapes = (params, jax.ShapeDtypeStruct((8192, 2048), jnp.bfloat16),
              jax.ShapeDtypeStruct((8192, 8), jnp.float32), jax.ShapeDtypeStruct((8192, 8), jnp.int32))
    with jax.default_matmul_precision("highest"):  # as tests/conftest.py sets it: part of the text
        return str(jax.make_jaxpr(lambda p, x, w, i: grouped_experts_apply(cfg, p, x, w, i))(*shapes))


def test_a_layer_that_holds_all_its_experts_keeps_its_straight_line_code():
    """Every row of such a layer is real, so the block loop has nothing to save it: no
    loop and no branch in its jaxpr, and no derivative of its own but the two row moves'
    (since PR 49 the dispatch and the combine gather over the sort and its inverse, forward
    and backward, where autodiff wrote scatter-adds). The digest moves with any edit to
    this path or to JAX's printer: look at the jaxpr, then record it anew."""
    text = _qwen_experts_jaxpr()
    assert "while" not in text and "cond" not in text
    assert re.findall(r"custom_vjp_call\[\s*name=(\w+)", text) == [
        "_copies_in_expert_order", "_weighted_sum_in_token_order"]
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "34dccedec5b3ea40f48b976335d582efdea5dd738a409586a43d110399a3a1ae")
    share = _qwen_experts_jaxpr({"n_held_experts": 16})
    assert "while" in share and "custom_vjp" in share  # the same call, told it holds a share
    # the share's code was not PR 49's to touch: letter for letter what 0985c04 traced
    assert hashlib.sha256(share.encode()).hexdigest() == (
        "c44101a7ac780577588dd3a8ff2289c0c0b30cf23eda02b9c2ed4c28c1642aaa")


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
