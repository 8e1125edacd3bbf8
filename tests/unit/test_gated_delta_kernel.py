"""The gated delta rule's kernels (``ops/pallas/gated_delta.py``) in interpret mode against
``chunk_gated_delta_rule_xla`` AND against the recurrence taken token by token in float64:
the output, the final state and EACH gradient on its own. The kernels may be as far from
the real numbers as the float32 XLA form they replace, not further. Tile-legal small
shapes (head widths 128, two value heads a key head so the chunk is 64). Three decays: the
benchmark cell's (g about -1.3: the state forgets in a token or two, so a fault in what
runs through the carried state hides at the percent level), a slow one over ten chunks
(where a test first shows that the carried state matters) and padded tokens (g = 0,
beta = 0). Then the adversarial inverse, bf16 inputs as the model hands them, shared key
heads against the materialised repeat, and where ``chunk_gated_delta_rule`` takes the
kernels and where it says why not."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.ops import kernels
from automodel_tpu.ops.gated_delta import (
    chunk_gated_delta_rule, chunk_gated_delta_rule_xla, l2norm)
from automodel_tpu.ops.pallas import gated_delta as gd

D = 128
NAMES = ("q", "k", "v", "g", "beta", "initial_state")
QUANTITIES = ("out", "final_state") + tuple("d_" + n for n in NAMES)


def _inputs(seed, *, B=1, S=192, Hk=1, r=2, g_mean=-1.3, padded=0, parallel_keys=False,
            beta=None, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    H = Hk * r
    q, k, v = rng.randn(B, S, Hk, D), rng.randn(B, S, Hk, D), rng.randn(B, S, H, D)
    if parallel_keys:  # every key of a head within a part in a thousand of one direction
        k = rng.randn(B, 1, Hk, D) + 1e-3 * k
    g = g_mean * np.exp(0.3 * rng.randn(B, S, H))
    b = 1 / (1 + np.exp(-rng.randn(B, S, H))) if beta is None else np.full((B, S, H), beta)
    if padded:  # right padding as the model neutralises it: decay 1, no write
        g[:, -padded:] = 0.0
        b[:, -padded:] = 0.0
    state = 0.1 * rng.randn(B, H, D, D)
    f32 = jnp.float32
    return [jnp.asarray(q, dtype), jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(g, f32), jnp.asarray(b, f32), jnp.asarray(state, f32)]


def _token_by_token(q, k, v, g, beta, state):
    """S_t = e^{g_t} S_{t-1}; S_t += k_t beta_t (v_t - k_t^T S_t)^T; o_t = q_t^T S_t, with q
    and k L2-normed, q scaled by dk^-1/2, key head h // r under value head h. In the dtype
    it is given: float64 under ``enable_x64``."""
    r = v.shape[2] // q.shape[2]
    norm = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)  # noqa: E731
    q = jnp.repeat(norm(q), r, axis=2) * q.shape[-1] ** -0.5
    k = jnp.repeat(norm(k), r, axis=2)

    def token(s, args):
        q_t, k_t, v_t, g_t, b_t = args  # (B, H, d) and (B, H)
        s = s * jnp.exp(g_t)[..., None, None]
        delta = (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, s)) * b_t[..., None]
        s = s + k_t[..., :, None] * delta[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s)

    s, o = jax.lax.scan(token, state, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def _kernel(*args):
    return gd.gated_delta_rule(*args[:5], initial_state=args[5], output_final_state=True,
                               interpret=True)


def _xla(*args):
    return chunk_gated_delta_rule_xla(*args[:5], initial_state=args[5], output_final_state=True)


def _all(fn, args, weights):
    """(out, final state, the six gradients) of ``fn`` under one scalar loss."""
    def loss(*a):
        o, s = fn(*a)
        return jnp.sum(o.astype(weights.dtype) * weights) + 0.3 * jnp.sum(s), (o, s)

    grads, (o, s) = jax.grad(loss, argnums=tuple(range(6)), has_aux=True)(*args)
    return (o, s, *grads)


CASES = {
    "cell_decay": dict(g_mean=-1.3),
    "slow_decay_ten_chunks": dict(g_mean=-0.01, S=640),
    "padded_tokens": dict(g_mean=-1.3, padded=40),
    "two_key_heads_two_rows": dict(g_mean=-0.3, Hk=2, B=2, S=128),
    "parallel_keys": dict(g_mean=-1e-3, parallel_keys=True, beta=0.999, S=128),
}


@functools.lru_cache(maxsize=None)
def _case(name):
    """Distances from the float64 recurrence, over its norm: (kernels, XLA form) a quantity."""
    args = _inputs(11, **CASES[name])
    weights = np.random.RandomState(4).randn(*args[2].shape)
    with jax.default_matmul_precision("highest"):
        got = _all(_kernel, args, jnp.asarray(weights, jnp.float32))
        xla = _all(_xla, args, jnp.asarray(weights, jnp.float32))
    with jax.enable_x64(True):
        want = _all(_token_by_token, [jnp.asarray(np.asarray(a), jnp.float64) for a in args],
                    jnp.asarray(weights, jnp.float64))
        want = [np.asarray(w) for w in want]

    def distance(x, w):
        return float(np.linalg.norm(np.asarray(x, np.float64) - w) / np.linalg.norm(w))

    return {n: (distance(g, w), distance(x, w)) for n, g, x, w in zip(QUANTITIES, got, xla, want)}


@pytest.mark.parametrize("quantity", QUANTITIES)
@pytest.mark.parametrize("case", list(CASES))
def test_kernels_are_no_further_from_the_float64_recurrence_than_the_xla_form(case, quantity):
    kernel, xla = _case(case)[quantity]
    assert xla < 1e-3, "the XLA form itself is off: the case is broken"
    assert kernel <= max(1e-5, 2 * xla), (kernel, xla)


def test_the_carried_state_matters_in_the_ten_chunk_case():
    """More than a tenth of what the last chunk puts out was written chunks ago: the slow
    case cannot pass with a state that is dropped, decayed wrongly or handed on late."""
    args = _inputs(11, **CASES["slow_decay_ten_chunks"])
    chunk = gd.LANES // 2
    with jax.default_matmul_precision("highest"):
        whole, _ = gd.gated_delta_rule(*args[:5], interpret=True)
        alone, _ = gd.gated_delta_rule(*(a[:, -chunk:] for a in args[:5]), interpret=True)
    carried = np.linalg.norm(np.asarray(whole[:, -chunk:] - alone))
    assert carried > 0.1 * np.linalg.norm(np.asarray(whole[:, -chunk:]))


def test_the_inverse_of_nearly_parallel_keys_is_as_good_as_solve_triangular():
    """Keys of a chunk nearly parallel, beta 0.999, g -1e-3: ``A`` is all but a strictly
    lower table of ones, whose power series cancels catastrophically in float32 (its terms
    are binomial coefficients). Against the table ``A`` in float64, the kernels' ``T``
    (what the gradient's forward saves) leaves a residual ``(I + A) T - I`` no larger than
    twice that of the XLA form's own ``T``: ``solve_triangular`` of its float32 ``A``."""
    q, k, v, g, beta, state = _inputs(11, **CASES["parallel_keys"])
    B, S, Hk, _ = q.shape
    r, C = v.shape[2] // Hk, gd.LANES // (v.shape[2] // Hk)
    highest = jax.lax.Precision.HIGHEST
    sq = lambda x: jnp.sum(jnp.square(x), -1)  # noqa: E731
    cs = jnp.cumsum(g.reshape(B, S // C, C, Hk * r), axis=2).reshape(g.shape)
    with jax.default_matmul_precision("highest"):
        _, (*_, saved) = gd._rule_fwd(q, k, v, cs, beta, jax.lax.rsqrt(sq(q) + 1e-6) * D ** -0.5,
                                      jax.lax.rsqrt(sq(k) + 1e-6), state, True)
    k32 = l2norm(k)
    k64 = np.asarray(k, np.float64)
    k64 = k64 / np.sqrt((k64 * k64).sum(-1, keepdims=True) + 1e-6)
    eye = np.eye(C)
    worst_kernel = worst_solve = 0.0
    for n in range(S // C):
        for a in range(r):
            rows = slice(n * C, (n + 1) * C)
            kc, c, b = (k64[0, rows, 0], np.asarray(cs, np.float64)[0, rows, a],
                        np.asarray(beta, np.float64)[0, rows, a])
            A = np.tril((b[:, None] * (kc @ kc.T)) * np.exp(c[:, None] - c[None, :]), -1)
            assert A[C - 1, 0] > 0.9  # the adversarial table it is meant to be
            T = np.asarray(saved, np.float64)[0, rows, a * C:(a + 1) * C]
            # the XLA form's own: ops/gated_delta.py's float32 A, then solve_triangular
            kc32, c32 = k32[0, rows, 0], cs[0, rows, a]
            A32 = jnp.tril(jnp.einsum("cd,md->cm", kc32 * beta[0, rows, a][:, None], kc32,
                                      precision=highest)
                           * jnp.exp(c32[:, None] - c32[None, :]), -1)
            solved = jax.scipy.linalg.solve_triangular(
                jnp.eye(C, dtype=jnp.float32) + A32, jnp.eye(C, dtype=jnp.float32), lower=True)
            worst_kernel = max(worst_kernel, np.abs((eye + A) @ T - eye).max())
            worst_solve = max(worst_solve, np.abs((eye + A) @ np.asarray(solved, np.float64)
                                                  - eye).max())
    assert worst_kernel <= 2 * worst_solve, (worst_kernel, worst_solve)
    assert worst_kernel < 2e-6


@pytest.mark.parametrize("g_mean", [-1.3, -0.01], ids=["cell_decay", "slow_decay"])
def test_bf16_inputs_agree_with_the_xla_form_to_one_rounding_of_the_output(g_mean):
    """Both take the same bf16 q, k, v and keep everything else in float32: the output
    differs from the XLA form's by at most one bf16 step where a float32 difference moves
    a rounding. dv, dg, dbeta and the state's lie within 2e-3 of the XLA form's, over its
    norm. dq and dk are held to the gradient the XLA form gives for float32 copies of the
    same values, which is not rounded at the end: within 3e-3 of its norm (one bf16
    rounding reads 2.4e-3) and no further than the XLA form's own, which rounds them three
    times (each value head's share, then their sum over the materialised ``jnp.repeat``)
    where the kernels round once."""
    args = _inputs(5, S=256, g_mean=g_mean, dtype=jnp.bfloat16)
    weights = jnp.asarray(np.random.RandomState(4).randn(*args[2].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, xla = _all(_kernel, args, weights), _all(_xla, args, weights)
        want = _all(_xla, [a.astype(jnp.float32) for a in args], weights)
    assert got[0].dtype == jnp.bfloat16
    a, b = np.asarray(got[0], np.float32), np.asarray(xla[0], np.float32)
    one_step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(b), 1e-30))) - 7)  # bf16: 8 bits
    assert np.all(np.abs(a - b) <= one_step + 1e-6)
    assert np.mean(a != b) < 0.02  # and rarely that
    for name, g, x, w in zip(QUANTITIES[1:], got[1:], xla[1:], want[1:]):
        assert g.dtype == x.dtype, name
        g, x, w = (np.asarray(t, np.float64) for t in (g, x, w))
        if name in ("d_q", "d_k"):
            distance = np.linalg.norm(g - w) / np.linalg.norm(w)
            assert distance <= 3e-3, (name, distance)
            assert distance <= 1.05 * np.linalg.norm(x - w) / np.linalg.norm(w) + 1e-5, name
        else:
            assert np.linalg.norm(g - x) <= 2e-3 * np.linalg.norm(x), name


@pytest.mark.parametrize("r", [2, 4])
def test_value_heads_that_share_a_key_head_give_what_the_materialised_repeat_gives(r):
    """q and k read once a key head, ``beta`` and ``g`` different on every value head:
    the same numbers as the rule on ``jnp.repeat``-ed q and k (one value head a key head,
    so the kernels' chunk is 128 there and 128 // r here: the rule is the same for any)."""
    args = _inputs(7, S=256, r=r, g_mean=-0.2)
    repeated = [jnp.repeat(args[0], r, axis=2), jnp.repeat(args[1], r, axis=2), *args[2:]]
    with jax.default_matmul_precision("highest"):
        shared = _kernel(*args)
        alone = _kernel(*repeated)
        xla = _xla(*repeated)
    for x, y, z in zip(shared, alone, xla):
        np.testing.assert_allclose(x, y, atol=2e-6, rtol=1e-5)
        np.testing.assert_allclose(x, z, atol=2e-6, rtol=1e-5)


# ---- which implementation chunk_gated_delta_rule takes, and what it records


@pytest.fixture
def fresh_record():
    kernels.reset()
    yield
    kernels.reset()


def _small(**kw):
    return _inputs(0, S=128, **kw)


def test_off_the_tpu_the_site_takes_the_xla_form_and_says_so(fresh_record):
    args = _small()
    o, _ = chunk_gated_delta_rule(*args[:5])
    np.testing.assert_array_equal(o, chunk_gated_delta_rule_xla(*args[:5])[0])
    snap = kernels.snapshot()
    assert snap["gated_delta"] == "xla" and not snap["interpret"]
    assert "default backend is cpu, not tpu" in snap["reasons"]["gated_delta"][0]


def test_on_a_mesh_of_several_devices_the_site_falls_back_by_name(fresh_record):
    args = _small()
    mesh = jax.make_mesh((2,), ("dp_shard",), devices=jax.devices()[:2])
    chunk_gated_delta_rule(*args[:5], mesh=mesh, interpret=True)
    snap = kernels.snapshot()
    assert snap["gated_delta"] == "xla"
    assert "a mesh of 2 devices" in snap["reasons"]["gated_delta"][0]
    kernels.reset()
    with jax.sharding.set_mesh(mesh):  # a mesh the call was not told of but can see
        chunk_gated_delta_rule(*args[:5], interpret=True)
    assert "a mesh of 2 devices" in kernels.snapshot()["reasons"]["gated_delta"][0]


def _narrow(args, d):
    return [args[0][..., :d], args[1][..., :d], *args[2:]]


@pytest.mark.parametrize(
    "make,options,reason",
    [
        (lambda: _narrow(_small(), 64), {}, "head widths 64/128 are not multiples of 128"),
        (lambda: [a[:, :100] if a.ndim > 2 and a.shape[1] == 128 else a for a in _small()], {},
         "sequence 100 is not a multiple of the chunk 64"),
        (lambda: _small(r=3), {}, "3 value heads over 1 key heads"),
        (_small, {"use_qk_l2norm": False}, "the kernels norm q and k themselves"),
    ],
    ids=["head_width", "sequence", "heads", "no_l2norm"],
)
def test_at_a_call_the_kernels_do_not_serve_the_site_falls_back_with_the_reason(
        fresh_record, make, options, reason):
    args = make()
    state = args[5][..., :args[0].shape[-1], :]
    o, _ = chunk_gated_delta_rule(*args[:5], initial_state=state, interpret=True, **options)
    np.testing.assert_array_equal(
        o, chunk_gated_delta_rule_xla(*args[:5], initial_state=state, **options)[0])
    snap = kernels.snapshot()
    assert snap["gated_delta"] == "xla"
    assert reason in snap["reasons"]["gated_delta"][0]


def test_aligned_on_one_device_the_site_takes_the_kernels_and_carries_the_state(fresh_record):
    args = _small()
    mesh = jax.make_mesh((1,), ("dp_shard",), devices=jax.devices()[:1])
    o, state = chunk_gated_delta_rule(*args[:5], mesh=mesh, interpret=True,
                                      initial_state=args[5], output_final_state=True)
    snap = kernels.snapshot()
    assert snap["gated_delta"] == "pallas" and snap["interpret"]
    assert "gated_delta" not in snap["reasons"]
    with jax.default_matmul_precision("highest"):
        want_o, want_state = _xla(*args)
    np.testing.assert_allclose(o, want_o, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-6, rtol=1e-5)
