"""MoE stack: routing semantics, grouped experts vs naive reference, EP dispatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.moe import (
    MoEConfig,
    fake_balanced_route,
    grouped_experts_apply,
    init_expert_params,
    init_gate_params,
    init_moe_params,
    moe_forward,
    route,
    update_gate_bias,
)
from automodel_tpu.moe import experts as experts_mod
from automodel_tpu.moe.experts import capacity_experts_apply, expert_activation
from automodel_tpu.moe.metrics import compute_load_balance_metrics


def small_cfg(**kw):
    base = dict(n_routed_experts=8, n_activated_experts=2, dim=16, moe_inter_dim=32)
    base.update(kw)
    return MoEConfig(**base)


def naive_experts(cfg, params, x, weights, indices):
    """Per-expert python-loop reference (mirrors reference _forward_loop semantics)."""
    x = np.asarray(x, np.float32)
    w_gu = np.asarray(params["gate_up_proj"], np.float32)
    w_d = np.asarray(params["down_proj"], np.float32)
    T, D = x.shape
    y = np.zeros((T, D), np.float32)
    for t in range(T):
        for k in range(indices.shape[1]):
            e = int(indices[t, k])
            h = x[t] @ w_gu[e]
            if "gate_up_bias" in params:
                h = h + np.asarray(params["gate_up_bias"], np.float32)[e]
            a = np.asarray(expert_activation(cfg, jnp.asarray(h)), np.float32)
            out = a @ w_d[e]
            if "down_bias" in params:
                out = out + np.asarray(params["down_bias"], np.float32)[e]
            y[t] += float(weights[t, k]) * out
    return y


class TestRoute:
    def test_softmax_topk_after(self):
        cfg = small_cfg(score_func="softmax")
        gp = init_gate_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (10, cfg.dim))
        w, idx, aux, load = route(cfg, gp, x)
        assert w.shape == (10, 2) and idx.shape == (10, 2)
        # weights are a softmax over the top-k values -> sum to 1
        np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
        assert aux is None
        assert float(load.sum()) == 20.0  # T * K valid tokens

    def test_softmax_before_topk(self):
        cfg = small_cfg(score_func="softmax", softmax_before_topk=True)
        gp = init_gate_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (10, cfg.dim))
        w, idx, _, _ = route(cfg, gp, x)
        # weights are probabilities of the full softmax -> sum < 1
        assert np.all(np.asarray(w.sum(-1)) < 1.0)
        # top-1 weight >= top-2
        assert np.all(np.asarray(w[:, 0]) >= np.asarray(w[:, 1]))

    def test_sigmoid_weights_are_sigmoid_scores(self):
        cfg = small_cfg(score_func="sigmoid", route_scale=2.5)
        gp = init_gate_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (6, cfg.dim))
        w, idx, _, _ = route(cfg, gp, x)
        scores = jax.nn.sigmoid(x @ gp["weight"].T)
        expect = np.take_along_axis(np.asarray(scores), np.asarray(idx), axis=-1) * 2.5
        np.testing.assert_allclose(np.asarray(w), expect, rtol=1e-5)

    def test_correction_bias_changes_selection_not_weights(self):
        cfg = small_cfg(score_func="sigmoid", gate_bias_update_factor=0.01)
        gp = init_gate_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (32, cfg.dim))
        _, idx0, _, _ = route(cfg, gp, x)
        # huge bias on expert 3 -> every token must select it
        gp2 = dict(gp, score_correction_bias=gp["score_correction_bias"].at[3].set(100.0))
        w, idx, _, _ = route(cfg, gp2, x)
        assert np.all(np.any(np.asarray(idx) == 3, axis=-1))
        # but weights still come from unbiased sigmoid scores (noaux-tc contract)
        scores = jax.nn.sigmoid(x @ gp["weight"].T)
        expect = np.take_along_axis(np.asarray(scores), np.asarray(idx), axis=-1)
        np.testing.assert_allclose(np.asarray(w), expect, rtol=1e-5)

    def test_group_limited_routing(self):
        # 8 experts, 4 groups of 2, only 1 group allowed -> both picks in same group
        cfg = small_cfg(score_func="sigmoid", n_expert_groups=4, n_limited_groups=1)
        gp = init_gate_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (20, cfg.dim))
        _, idx, _, _ = route(cfg, gp, x)
        groups = np.asarray(idx) // 2
        assert np.all(groups[:, 0] == groups[:, 1])

    def test_norm_topk_prob(self):
        cfg = small_cfg(score_func="sigmoid", norm_topk_prob=True)
        gp = init_gate_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (10, cfg.dim))
        w, _, _, _ = route(cfg, gp, x)
        np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)

    def test_expert_load_respects_token_mask(self):
        cfg = small_cfg()
        gp = init_gate_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (10, cfg.dim))
        mask = jnp.array([True] * 4 + [False] * 6)
        _, _, _, load = route(cfg, gp, x, mask)
        assert float(load.sum()) == 4 * cfg.n_activated_experts

    def test_aux_loss_balanced_is_one(self):
        # perfectly uniform scores + balanced load -> f_i = 1, sum(f_i * P_i) = sum(P_i)
        cfg = small_cfg(aux_loss_coeff=0.01, score_func="softmax")
        gp = init_gate_params(cfg, jax.random.key(0))
        gp["weight"] = jnp.zeros_like(gp["weight"])  # all scores equal
        x = jax.random.normal(jax.random.key(1), (16, cfg.dim))
        _, _, aux, load = route(cfg, gp, x)
        assert aux is not None and np.isfinite(float(aux))

    def test_jit_and_grad(self):
        cfg = small_cfg(aux_loss_coeff=0.01, score_func="sigmoid", norm_topk_prob=True)
        gp = init_gate_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (10, cfg.dim))

        def loss(gp):
            w, _, aux, _ = route(cfg, gp, x)
            return w.sum() + aux

        g = jax.jit(jax.grad(loss))(gp)
        assert np.isfinite(np.asarray(g["weight"])).all()


class TestFakeBalancedGate:
    def test_perfectly_balanced(self):
        cfg = small_cfg()
        x = jax.random.normal(jax.random.key(0), (16, cfg.dim))
        w, idx, aux, load = fake_balanced_route(cfg, x)
        assert aux is None
        np.testing.assert_allclose(np.asarray(w), 1.0 / cfg.n_activated_experts)
        np.testing.assert_allclose(np.asarray(load), load.sum() / cfg.n_routed_experts)

    def test_noise_is_content_deterministic(self):
        cfg = small_cfg()
        x = jax.random.normal(jax.random.key(0), (16, cfg.dim))
        _, idx1, _, _ = fake_balanced_route(cfg, x, noise=0.5)
        _, idx2, _, _ = fake_balanced_route(cfg, x, noise=0.5)
        np.testing.assert_array_equal(np.asarray(idx1), np.asarray(idx2))
        # unique experts per token (required by scatter-back)
        for row in np.asarray(idx1):
            assert len(set(row.tolist())) == len(row)


class TestGroupedExperts:
    @pytest.mark.parametrize("activation", ["swiglu", "quick_geglu", "relu2"])
    def test_matches_naive_loop(self, activation):
        cfg = small_cfg(expert_activation=activation, expert_bias=(activation == "quick_geglu"))
        ep = init_expert_params(cfg, jax.random.key(0))
        gp = init_gate_params(cfg, jax.random.key(1))
        x = jax.random.normal(jax.random.key(2), (12, cfg.dim))
        w, idx, _, _ = route(cfg, gp, x)
        got = grouped_experts_apply(cfg, ep, x, w, idx)
        want = naive_experts(cfg, ep, x, np.asarray(w), np.asarray(idx))
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)

    def test_capacity_path_matches_when_no_drops(self):
        cfg = small_cfg()
        ep = init_expert_params(cfg, jax.random.key(0))
        gp = init_gate_params(cfg, jax.random.key(1))
        x = jax.random.normal(jax.random.key(2), (12, cfg.dim))
        w, idx, _, _ = route(cfg, gp, x)
        dropless = grouped_experts_apply(cfg, ep, x, w, idx)
        # capacity = T*K guarantees no drops
        capped = capacity_experts_apply(cfg, ep, x, w, idx, capacity=24)
        np.testing.assert_allclose(np.asarray(capped), np.asarray(dropless), atol=1e-4)

    def test_capacity_drops_overflow(self):
        cfg = small_cfg()
        ep = init_expert_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(2), (12, cfg.dim))
        # route everything to expert 0 with capacity 1 -> only first token contributes
        idx = jnp.zeros((12, 2), jnp.int32)
        w = jnp.ones((12, 2)) * 0.5
        out = capacity_experts_apply(cfg, ep, x, w, idx, capacity=1)
        assert np.abs(np.asarray(out[2:])).max() == 0.0
        assert np.abs(np.asarray(out[0])).max() > 0.0

    def test_masked_tokens_do_not_consume_capacity(self):
        cfg = small_cfg()
        ep = init_expert_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(2), (12, cfg.dim))
        idx = jnp.zeros((12, 2), jnp.int32)  # everyone wants expert 0
        w = jnp.ones((12, 2)) * 0.5
        # first 10 tokens masked out; capacity 2 -> the two valid tokens get the slots
        mask = jnp.array([False] * 10 + [True] * 2)
        out = capacity_experts_apply(cfg, ep, x, w, idx, mask, capacity=2)
        assert np.abs(np.asarray(out[:10])).max() == 0.0
        assert np.abs(np.asarray(out[10:])).max() > 0.0

    def test_grad_flows(self):
        cfg = small_cfg()
        ep = init_expert_params(cfg, jax.random.key(0))
        gp = init_gate_params(cfg, jax.random.key(1))
        x = jax.random.normal(jax.random.key(2), (8, cfg.dim))

        def loss(ep, x):
            w, idx, _, _ = route(cfg, gp, x)
            return grouped_experts_apply(cfg, ep, x, w, idx).sum()

        g_ep, g_x = jax.jit(jax.grad(loss, argnums=(0, 1)))(ep, x)
        assert np.isfinite(np.asarray(g_ep["gate_up_proj"])).all()
        assert np.abs(np.asarray(g_x)).max() > 0


def _per_token_loop(cfg, params, x, weights, indices, token_mask=None):
    """The dropless block in plain float32, token by token and pick by pick: nothing is
    sorted, gathered or scattered, so it shares no row move with the code under test."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    mask = jnp.ones(x.shape[0], bool) if token_mask is None else token_mask

    def token(x_t, w_t, idx_t, m_t):
        y = jnp.zeros_like(x_t)
        for k in range(indices.shape[1]):
            h = x_t @ p["gate_up_proj"][idx_t[k]]
            y = y + w_t[k] * (expert_activation(cfg, h) @ p["down_proj"][idx_t[k]])
        return y * m_t

    return jax.vmap(token)(x.astype(jnp.float32), weights.astype(jnp.float32), indices, mask)


def _straight_line_case(name):
    """``(cfg, params, x, weights, indices, token_mask)`` of one case of the branch a
    layer that holds all its experts takes."""
    T, K, E, dtype, mask = 24, 2, 8, jnp.float32, None
    if name in ("top_1", "top_8"):
        K = int(name[4:])
    if name == "rows_not_a_multiple_of_128":
        T, K = 43, 3  # 129 rows
    if name == "bf16":
        T, K, dtype = 32, 8, jnp.bfloat16
    cfg = small_cfg(n_routed_experts=E, n_activated_experts=K)
    keys = jax.random.split(jax.random.key(len(name)), 4)
    params = init_expert_params(cfg, keys[0], dtype, init_std=0.3)
    x = jax.random.normal(keys[1], (T, cfg.dim)).astype(dtype)
    scores = jax.random.normal(keys[2], (T, E))
    if name == "an_expert_with_no_rows":
        scores = scores.at[:, 3].set(-1e9)
    top, indices = jax.lax.top_k(scores, K)
    weights = jax.nn.softmax(top, axis=-1)
    if name == "every_token_to_one_expert":
        indices = jnp.full((T, K), 5, jnp.int32)  # the same expert K times a token
    if name == "two_picks_of_equal_weight":
        weights = weights.at[:, 1].set(weights[:, 0])
    if name == "masked_tokens":
        mask = jnp.arange(T) % 3 != 1
    return cfg, params, x, weights, indices.astype(jnp.int32), mask


class TestStraightLineRowMoves:
    """The branch of ``grouped_experts_apply`` that a layer holding all its experts takes
    moves rows by gathers over the sort and its inverse, forward and backward."""

    @pytest.mark.parametrize("case", [
        "top_1", "top_2", "top_8", "every_token_to_one_expert", "an_expert_with_no_rows",
        "two_picks_of_equal_weight", "masked_tokens", "rows_not_a_multiple_of_128", "bf16"])
    def test_value_and_gradients_match_a_per_token_loop(self, case):
        cfg, params, x, weights, indices, mask = _straight_line_case(case)
        probe = jax.random.normal(jax.random.key(9), x.shape)  # gradients of mixed sign

        def loss(fn):
            def f(params, x, weights):
                y = fn(cfg, params, x, weights, indices, mask)
                return (y.astype(jnp.float32) * probe).sum(), y
            return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

        (_, y), (g_p, g_x, g_w) = jax.jit(loss(grouped_experts_apply))(params, x, weights)
        (_, want), (w_p, w_x, w_w) = jax.jit(loss(_per_token_loop))(params, x, weights)
        assert y.dtype == x.dtype and g_x.dtype == x.dtype and g_w.dtype == weights.dtype

        def close(got, want):
            got = np.asarray(got, np.float32)
            if case == "bf16":  # the FFN between the moves rounds to 8 bits: by norm
                assert np.linalg.norm(got - want) <= 2e-2 * np.linalg.norm(want)
            else:
                np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

        close(y, want), close(g_x, w_x), close(g_w, w_w)
        close(g_p["gate_up_proj"], w_p["gate_up_proj"]), close(g_p["down_proj"], w_p["down_proj"])
        if mask is not None:
            assert not np.asarray(y)[~np.asarray(mask)].any()
            assert not np.asarray(g_w)[~np.asarray(mask)].any()
        if case == "bf16":
            # the dispatch's own gradient: each token is picked by K = 8 experts and the
            # copies' cotangents differ in sign, so a bf16 running sum rounds seven times
            # where the float32 sum over K rounds once
            K = indices.shape[1]
            rows = jnp.argsort(indices.reshape(-1))
            inv = jnp.argsort(rows)
            dxs = jax.random.normal(jax.random.key(3), (rows.shape[0], x.shape[1])).astype(x.dtype)
            exact = np.zeros(x.shape, np.float64)
            np.add.at(exact, np.asarray(rows) // K, np.asarray(dxs, np.float64))
            (new,) = jax.vjp(lambda x: experts_mod._copies_in_expert_order(x, rows, inv, K), x)[1](dxs)
            # the tree before PR 49: a plain gather, transposed to a scatter-add in x's dtype
            (old,) = jax.vjp(lambda x: x[rows // K], x)[1](dxs)
            err = lambda g: np.abs(np.asarray(g, np.float64) - exact)
            assert new.dtype == old.dtype == x.dtype
            assert err(new).max() <= err(old).max() and err(new).sum() < err(old).sum()

    def test_no_row_scatter_add_is_left(self):
        """Value and gradients of the branch hold no ``scatter-add`` on a rank-2 float
        operand (the sort's bincount, rank 1 and integer, stays) and no integer scatter
        (the inverse is a sort). That the held share's jaxpr is the parent's letter for
        letter: ``test_moe_held_experts.py``, beside the straight line's digest."""
        import re

        cfg, params, x, weights, indices, _ = _straight_line_case("top_8")

        def loss(params, x, weights):
            return grouped_experts_apply(cfg, params, x, weights, indices).astype(jnp.float32).sum()

        text = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(params, x, weights))
        outs = re.findall(r"(\w+)\[([\d,]*)\] = scatter-add\[", text)
        assert outs, "the bincount of the sort is a scatter-add: the pattern must see it"
        assert all(dtype.startswith("i") and "," not in shape for dtype, shape in outs), outs
        assert " scatter[" not in text


class TestMoEForward:
    def test_shared_experts_and_shapes(self):
        cfg = small_cfg(n_shared_experts=2, shared_expert_gate=True)
        params = init_moe_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (2, 6, cfg.dim))
        y, aux, load = moe_forward(cfg, params, x)
        assert y.shape == x.shape
        assert load.shape == (cfg.n_routed_experts,)
        # shared experts contribute: zeroing them changes the output
        params2 = dict(params)
        params2["shared_experts"] = jax.tree.map(jnp.zeros_like, params["shared_experts"])
        y2, _, _ = moe_forward(cfg, params2, x)
        assert np.abs(np.asarray(y - y2)).max() > 0

    def test_aux_loss_emitted_in_training(self):
        cfg = small_cfg(aux_loss_coeff=0.01)
        params = init_moe_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (2, 6, cfg.dim))
        _, aux, _ = moe_forward(cfg, params, x, training=True)
        assert aux is not None
        _, aux_eval, _ = moe_forward(cfg, params, x, training=False)
        assert aux_eval is None

    def test_fake_gate(self):
        cfg = small_cfg()
        params = init_moe_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (2, 8, cfg.dim))
        y, _, load = moe_forward(cfg, params, x, fake_balanced_gate=True)
        np.testing.assert_allclose(np.asarray(load), load.sum() / cfg.n_routed_experts)


class TestGateBiasUpdate:
    def test_sign_update(self):
        bias = jnp.zeros(4)
        load = jnp.array([10.0, 0.0, 5.0, 5.0])  # mean 5
        new = update_gate_bias(bias, load, 0.1)
        np.testing.assert_allclose(np.asarray(new), [-0.1, 0.1, 0.0, 0.0], atol=1e-7)


class TestMetrics:
    def test_balanced_load(self):
        m = compute_load_balance_metrics(np.full((3, 8), 10.0))
        assert m["moe_load/max_util_mean"] == 1.0
        assert m["moe_load/zero_expert_frac"] == 0.0

    def test_imbalanced(self):
        loads = np.zeros((1, 4))
        loads[0, 0] = 8.0
        m = compute_load_balance_metrics(loads, mode="detailed")
        assert m["moe_load/max_util_mean"] == 4.0
        assert m["moe_load/zero_expert_frac"] == 0.75
        assert "moe_load/layer0/max_util" in m


class TestEPDispatch:
    def test_matches_dropless_on_ep_mesh(self, cpu_devices):
        from automodel_tpu.moe.dispatch import make_ep_moe_forward
        from automodel_tpu.parallel.mesh import MeshContext

        ctx = MeshContext(ep=4, dp_shard=2, world_size=8)
        mesh = ctx.build_mesh(cpu_devices)
        cfg = small_cfg(n_routed_experts=8, n_activated_experts=2, n_shared_experts=1)
        params = init_moe_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (8, 4, cfg.dim))

        # generous capacity -> no drops -> exact match with the dropless GSPMD path
        fn = make_ep_moe_forward(cfg, mesh, capacity=64)
        with jax.sharding.set_mesh(mesh):
            y, aux, load, dropped = fn(params, x)
        ref_y, _, ref_load = moe_forward(cfg, params, x)
        assert float(dropped) == 0.0
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y), atol=2e-4)
        np.testing.assert_allclose(np.asarray(load), np.asarray(ref_load))

    def test_masked_tokens_dropped(self, cpu_devices):
        from automodel_tpu.moe.dispatch import make_ep_moe_forward
        from automodel_tpu.parallel.mesh import MeshContext

        ctx = MeshContext(ep=4, dp_shard=2, world_size=8)
        mesh = ctx.build_mesh(cpu_devices)
        cfg = small_cfg()
        params = init_moe_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (8, 4, cfg.dim))
        token_mask = jnp.ones((8, 4), bool).at[:, 2:].set(False)
        fn = make_ep_moe_forward(cfg, mesh, capacity=64)
        with jax.sharding.set_mesh(mesh):
            y, _, load, _ = fn(params, x, token_mask)
        # masked positions produce zero routed output (no shared experts configured)
        assert np.abs(np.asarray(y[:, 2:])).max() == 0.0
        assert np.abs(np.asarray(y[:, :2])).max() > 0.0
        assert float(load.sum()) == 8 * 2 * cfg.n_activated_experts

    def test_grad_through_dispatch(self, cpu_devices):
        from automodel_tpu.moe.dispatch import make_ep_moe_forward
        from automodel_tpu.parallel.mesh import MeshContext

        ctx = MeshContext(ep=2, dp_shard=4, world_size=8)
        mesh = ctx.build_mesh(cpu_devices)
        cfg = small_cfg(n_routed_experts=4)
        params = init_moe_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (4, 4, cfg.dim))
        fn = make_ep_moe_forward(cfg, mesh, capacity=64)

        def loss(params):
            y, _, _, _ = fn(params, x)
            return (y**2).sum()

        with jax.sharding.set_mesh(mesh):
            g = jax.jit(jax.grad(loss))(params)
        assert np.isfinite(np.asarray(g["experts"]["gate_up_proj"])).all()
        assert np.abs(np.asarray(g["experts"]["down_proj"])).max() > 0


class TestEPDispatchDropAccounting:
    def test_ample_capacity_reports_zero(self, cpu_devices):
        from automodel_tpu.moe.dispatch import make_ep_moe_forward
        from automodel_tpu.parallel.mesh import MeshContext

        ctx = MeshContext(ep=4, dp_shard=2, world_size=8)
        mesh = ctx.build_mesh(cpu_devices)
        cfg = small_cfg()
        params = init_moe_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (8, 4, cfg.dim))
        fn = make_ep_moe_forward(cfg, mesh, capacity=64)
        with jax.sharding.set_mesh(mesh):
            _, _, _, dropped = fn(params, x)
        assert float(dropped) == 0.0

    def test_tight_capacity_reports_drops(self, cpu_devices):
        from automodel_tpu.moe.dispatch import make_ep_moe_forward
        from automodel_tpu.parallel.mesh import MeshContext

        ctx = MeshContext(ep=4, dp_shard=2, world_size=8)
        mesh = ctx.build_mesh(cpu_devices)
        cfg = small_cfg()
        params = init_moe_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (8, 4, cfg.dim))
        fn = make_ep_moe_forward(cfg, mesh, capacity=1)
        with jax.sharding.set_mesh(mesh):
            _, _, load, dropped = fn(params, x)
        # per ep-shard: 8 tokens x K=2 copies but each of 4 destinations keeps <=1
        assert 0.0 < float(dropped) <= 1.0
        # kept copies = valid - dropped: the load psum counts ROUTED (pre-drop) tokens
        assert float(load.sum()) == 8 * 4 * cfg.n_activated_experts

    def test_model_level_a2a_wiring(self, cpu_devices):
        """backend.dispatcher='a2a' routes the common MoE stack through EP a2a
        dispatch and surfaces stats['dropped_token_frac']; with ample capacity the
        logits match the GSPMD dense-dispatcher path."""
        from automodel_tpu.models.auto import AutoModelForCausalLM
        from automodel_tpu.models.common.backend import BackendConfig
        from automodel_tpu.parallel.mesh import MeshContext, default_sharding_rules

        hf_cfg = {
            "architectures": ["Qwen3MoeForCausalLM"],
            "vocab_size": 128, "hidden_size": 32, "intermediate_size": 48,
            "moe_intermediate_size": 16, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
            "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
            "max_position_embeddings": 32,
        }
        ctx = MeshContext(ep=4, dp_shard=2, world_size=8)
        mesh = ctx.build_mesh(cpu_devices)
        rules = default_sharding_rules().with_mesh(mesh)
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (8, 8)), jnp.int32)

        ref_model = AutoModelForCausalLM.from_config(
            hf_cfg, BackendConfig(dtype="float32")
        )
        params = ref_model.init(jax.random.key(1), jnp.float32)
        ref_logits, ref_stats = ref_model(params, ids, training=True)

        a2a_model = AutoModelForCausalLM.from_config(
            hf_cfg, BackendConfig(dtype="float32", dispatcher="a2a",
                                  ep_capacity_factor=8.0)
        )
        with jax.sharding.set_mesh(mesh):
            logits, stats = a2a_model(params, ids, rules=rules, training=True)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref_logits), atol=2e-4
        )
        assert float(stats["dropped_token_frac"]) == 0.0
        np.testing.assert_allclose(
            np.asarray(stats["expert_load"]), np.asarray(ref_stats["expert_load"])
        )


class TestChunkedDispatch:
    """a2a/compute overlap chunking (``backend.a2a_chunks``): routing, the
    capacity cutoff, and dropped_frac are computed globally BEFORE the send
    buffer is sliced, so any chunk count must reproduce the unchunked
    forward — and the activation/gate gradients — bit-for-bit. Expert WEIGHT
    grads accumulate per-chunk partial sums (a float reassociation, measured
    ~2e-7 relative; moe/dispatch.py docstring), so they get a tight allclose
    instead. An ep-only mesh keeps every >1 axis manual."""

    def _setup(self, cpu_devices):
        from automodel_tpu.parallel.mesh import MeshContext

        mesh = MeshContext(ep=8, world_size=8).build_mesh(cpu_devices)
        cfg = small_cfg(dim=32, moe_inter_dim=48, aux_loss_coeff=0.01)
        params = init_moe_params(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (8, 16, cfg.dim))
        mask = jnp.ones((8, 16), bool)
        return mesh, cfg, params, x, mask

    def test_chunked_forward_bit_identical(self, cpu_devices):
        from automodel_tpu.moe.dispatch import make_ep_moe_forward

        mesh, cfg, params, x, mask = self._setup(cpu_devices)
        results = {}
        with jax.sharding.set_mesh(mesh):
            for nch in (1, 2, 3, 4):
                fn = make_ep_moe_forward(cfg, mesh, n_chunks=nch)
                y, aux, load, dropped = jax.jit(fn)(params, x, mask)
                results[nch] = (np.asarray(y), float(aux), np.asarray(load),
                                float(dropped))
        ref = results[1]
        for nch in (2, 3, 4):
            y, aux, load, dropped = results[nch]
            assert np.array_equal(ref[0], y), f"n_chunks={nch} diverged"
            assert ref[1] == aux and ref[3] == dropped
            assert np.array_equal(ref[2], load)

    def test_chunked_loss_and_grads(self, cpu_devices):
        from automodel_tpu.moe.dispatch import make_ep_moe_forward

        mesh, cfg, params, x, mask = self._setup(cpu_devices)

        def loss(p, xin, nch):
            fn = make_ep_moe_forward(cfg, mesh, n_chunks=nch)
            y, aux, _, _ = fn(p, xin, mask)
            return jnp.sum(y * y) + 0.01 * aux

        with jax.sharding.set_mesh(mesh):
            l1, (gp1, gx1) = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)),
                                     static_argnums=2)(params, x, 1)
            l3, (gp3, gx3) = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)),
                                     static_argnums=2)(params, x, 3)
        assert float(l1) == float(l3)  # losses reproduce exactly
        # activation + gate grads are bit-identical (per-row independence)
        assert np.array_equal(np.asarray(gx1), np.asarray(gx3))
        assert np.array_equal(np.asarray(gp1["gate"]["weight"]),
                              np.asarray(gp3["gate"]["weight"]))
        # expert weight grads: per-chunk dw partial sums reassociate
        for k in ("gate_up_proj", "down_proj"):
            np.testing.assert_allclose(
                np.asarray(gp1["experts"][k]), np.asarray(gp3["experts"][k]),
                rtol=1e-5, atol=1e-6)

    def test_chunking_preserves_drop_accounting(self, cpu_devices):
        from automodel_tpu.moe.dispatch import make_ep_moe_forward

        mesh, cfg, params, x, mask = self._setup(cpu_devices)
        with jax.sharding.set_mesh(mesh):
            dropped = {
                nch: float(jax.jit(make_ep_moe_forward(
                    cfg, mesh, capacity=2, n_chunks=nch))(params, x, mask)[3])
                for nch in (1, 3)
            }
        # tight capacity drops copies; the count is chunk-invariant and exact
        assert 0.0 < dropped[1] <= 1.0
        assert dropped[1] == dropped[3]

    def test_pallas_experts_through_a2a_dispatch(self, cpu_devices):
        from automodel_tpu.moe.dispatch import make_ep_moe_forward

        mesh, cfg, params, x, mask = self._setup(cpu_devices)
        with jax.sharding.set_mesh(mesh):
            yr = jax.jit(make_ep_moe_forward(cfg, mesh, n_chunks=2))(
                params, x, mask)[0]
            yp = jax.jit(make_ep_moe_forward(
                cfg, mesh, n_chunks=2, experts_backend="pallas"))(
                params, x, mask)[0]
        np.testing.assert_allclose(np.asarray(yp), np.asarray(yr),
                                   atol=1e-5, rtol=1e-5)


def test_a2a_at_ep1_warns_with_measurement(caplog):
    """dispatcher='a2a' on a 1-rank ep axis logs the measured guidance
    (tools/bench_a2a_dispatch.py: 2.25x slower than dense on one chip)."""
    import logging

    import jax

    from automodel_tpu.models.common.backend import BackendConfig
    from automodel_tpu.moe.config import MoEConfig
    from automodel_tpu.moe.dispatch import make_moe_block_forward
    from automodel_tpu.parallel.mesh import MeshContext, default_sharding_rules

    ctx = MeshContext(ep=1, dp_shard=1, world_size=1)
    mesh = ctx.build_mesh(jax.devices()[:1])
    rules = default_sharding_rules().with_mesh(mesh)
    cfg = MoEConfig(n_routed_experts=4, n_activated_experts=2, dim=16,
                    moe_inter_dim=8)
    with caplog.at_level(logging.WARNING):
        make_moe_block_forward(cfg, BackendConfig(dispatcher="a2a"), rules)
    assert any("2.3x slower" in r.message for r in caplog.records)
