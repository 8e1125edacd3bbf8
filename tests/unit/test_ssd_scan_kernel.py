"""The SSD scan kernels (``ops/pallas/ssd_scan.py``) in interpret mode against
``mamba_chunk_scan_xla`` and against the recurrence taken token by token in float32:
outputs, the final state and every gradient. Tile-legal small shapes (chunk 128, state
128 or 256, head_dim 64 in pairs or 128 alone); the published initialisation runs ten chunks, so
a state that is dropped, decayed wrongly or handed on late fails here (see
``test_mamba2_published_init.py``). Then where ``mamba_chunk_scan`` takes the kernels and
where it says why not."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.ops import kernels
from automodel_tpu.ops.mamba2 import mamba_chunk_scan, mamba_chunk_scan_xla
from automodel_tpu.ops.pallas.ssd_scan import ssd_scan

CHUNK, N = 128, 128
NAMES = ("x", "dt", "A", "B", "C", "D", "initial_state")


def _inputs(seed, init, *, B=1, S=256, H=4, P=64, G=2, N=N, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    if init == "published":  # a state lives for 0.6 to 1000 tokens
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (B, S, H)))
        A = -rng.uniform(1.0, 16.0, (H,))
    else:  # the benchmark harness's: dt = softplus(N(0, 1.3)), A about -1
        dt = np.log1p(np.exp(1.3 * rng.randn(B, S, H)))
        A = -np.exp(0.02 * rng.randn(H))
    x, Bm, Cm = rng.randn(B, S, H, P), rng.randn(B, S, G, N), rng.randn(B, S, G, N)
    D = 1.0 + 0.1 * rng.randn(H)
    state = rng.randn(B, H, P, N)
    f32 = jnp.float32
    return [jnp.asarray(x, dtype), jnp.asarray(dt, f32), jnp.asarray(A, f32),
            jnp.asarray(Bm, dtype), jnp.asarray(Cm, dtype), jnp.asarray(D, f32),
            jnp.asarray(state, f32)]


def _token_by_token(x, dt, A, Bm, Cm, D, state, reset_mask=None):
    """h_t = exp(dt_t A - 50 reset_t) h_{t-1} + dt_t x_t B_t^T; y_t = h_t C_t + D x_t."""
    r = x.shape[2] // Bm.shape[2]
    reset = jnp.zeros(x.shape[:2]) if reset_mask is None else reset_mask.astype(jnp.float32)

    def token(h, args):
        x_t, dt_t, b_t, c_t, r_t = args
        b_h, c_h = jnp.repeat(b_t, r, axis=1), jnp.repeat(c_t, r, axis=1)
        decay = jnp.exp(dt_t * A - 50.0 * r_t[:, None])
        h = h * decay[..., None, None] + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None]
        return h, jnp.sum(h * c_h[:, :, None], -1) + D[:, None] * x_t

    h, y = jax.lax.scan(token, state, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm, reset)))
    return jnp.moveaxis(y, 0, 1), h


def _kernel(*args, **kw):
    return ssd_scan(*args[:6], chunk_size=CHUNK, initial_state=args[6],
                    output_final_state=True, interpret=True, **kw)


def _xla(*args, **kw):
    return mamba_chunk_scan_xla(*args[:6], chunk_size=CHUNK, initial_state=args[6],
                                output_final_state=True, **kw)


def _grads(fn, args, weights):
    def loss(*a):
        y, state = fn(*a)
        return jnp.sum(y.astype(jnp.float32) * weights) + 0.3 * jnp.sum(state)

    return jax.grad(loss, argnums=tuple(range(7)))(*args)


CASES = {
    "harness_init": dict(init="harness", S=256, B=2),
    "published_init_ten_chunks": dict(init="published", S=1280),
    "padded_sequence": dict(init="published", S=200),
    "reset_mask": dict(init="harness", S=256, reset=True),
    "head_dim_128": dict(init="published", S=256, H=2, P=128, G=1),
    # Falcon-H1's branch: a head a tile, several heads a group, a state of two lane tiles
    "head_dim_128_state_256": dict(init="published", S=256, H=4, P=128, G=2, N=256),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_are_the_recurrence_and_the_xla_form_in_float32(case):
    spec = dict(CASES[case])
    reset = spec.pop("reset", False)
    args = _inputs(11, **spec)
    shape = args[0].shape
    kw = {}
    if reset:
        mask = np.zeros(shape[:2], bool)
        mask[:, [37, 128, 201]] = True  # inside a chunk, at a chunk's start, inside the next
        kw["reset_mask"] = jnp.asarray(mask)
    weights = jnp.asarray(np.random.RandomState(4).randn(*shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want_y, want_state = _token_by_token(*args, **kw)
        xla_y, xla_state = _xla(*args, **kw)
        got_y, got_state = _kernel(*args, **kw)
        want = _grads(lambda *a: _token_by_token(*a, **kw), args, weights)
        xla = _grads(lambda *a: _xla(*a, **kw), args, weights)
        got = _grads(lambda *a: _kernel(*a, **kw), args, weights)
    for ref_y, ref_state in ((want_y, want_state), (xla_y, xla_state)):
        np.testing.assert_allclose(got_y, ref_y, atol=2e-4, rtol=1e-4)
        np.testing.assert_allclose(got_state, ref_state, atol=2e-4, rtol=1e-4)
    for ref in (want, xla):
        for name, g, w in zip(NAMES, got, ref):
            scale = float(np.abs(np.asarray(w)).max())
            np.testing.assert_allclose(g, w, atol=2e-4 * scale, rtol=1e-3, err_msg=name)


def test_the_carried_state_matters_in_the_ten_chunk_case():
    """About half of what the last chunk's first tokens read was written chunks ago (as in
    test_mamba2_published_init.py): the case above cannot pass with a broken hand-over."""
    args = _inputs(11, "published", S=1280)
    zero = jnp.zeros_like(args[6])
    with jax.default_matmul_precision("highest"):
        whole, _ = ssd_scan(*args[:6], chunk_size=CHUNK, interpret=True)
        alone, _ = ssd_scan(*(a[:, -CHUNK:] if a.ndim > 1 else a for a in args[:6]),
                            chunk_size=CHUNK, initial_state=zero, interpret=True)
    first = slice(-CHUNK, -CHUNK + 16)  # the chunk's first tokens: later ones read the chunk itself
    carried = np.abs(np.asarray(whole[:, first] - alone[:, :16])).mean()
    read_out = np.abs(np.asarray(whole[:, first] - args[5][:, None] * args[0][:, first])).mean()
    assert carried > 0.3 * read_out


@pytest.mark.parametrize("init", ["harness", "published"])
def test_bf16_inputs_agree_with_the_xla_form_to_one_rounding_of_the_output(init):
    """Both take the same bf16 x, B, C and keep everything else in float32: what differs is
    the order of float32 sums (2e-4, the float32 cases' tolerance), so y differs by at most
    one bf16 step where that moves a rounding."""
    args = _inputs(5, init, S=384, dtype=jnp.bfloat16)
    weights = jnp.asarray(np.random.RandomState(4).randn(*args[0].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        xla_y, xla_state = _xla(*args)
        got_y, got_state = _kernel(*args)
        xla = _grads(_xla, args, weights)
        got = _grads(_kernel, args, weights)
    assert got_y.dtype == jnp.bfloat16
    a, b = np.asarray(got_y, np.float32), np.asarray(xla_y, np.float32)
    one_step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(b), 1e-30))) - 7)  # bf16: 8 bits
    assert np.all(np.abs(a - b) <= one_step + 2e-4)
    assert np.mean(a != b) < 0.02  # and rarely that
    np.testing.assert_allclose(got_state, xla_state, atol=2e-4, rtol=1e-4)
    for name, g, w in zip(NAMES, got, xla):
        assert g.dtype == w.dtype, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        # bf16 gradients (x, B, C) round once more at the end; the float32 ones do not
        tol = 2.0 ** -7 if name in ("x", "B", "C") else 1e-3
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), name


# ---- which implementation mamba_chunk_scan takes, and what it records


@pytest.fixture
def fresh_record():
    kernels.reset()
    yield
    kernels.reset()


def _call(args, **kw):
    return mamba_chunk_scan(*args[:6], chunk_size=kw.pop("chunk_size", CHUNK), **kw)


def test_off_the_tpu_the_site_takes_the_xla_form_and_says_so(fresh_record):
    args = _inputs(0, "harness")
    y, _ = _call(args)
    np.testing.assert_array_equal(y, mamba_chunk_scan_xla(*args[:6], chunk_size=CHUNK)[0])
    snap = kernels.snapshot()
    assert snap["ssd_scan"] == "xla" and not snap["interpret"]
    assert "default backend is cpu, not tpu" in snap["reasons"]["ssd_scan"][0]


def test_on_a_mesh_of_several_devices_the_site_falls_back_by_name(fresh_record):
    args = _inputs(0, "harness")
    mesh = jax.make_mesh((2,), ("dp_shard",), devices=jax.devices()[:2])
    _call(args, mesh=mesh, interpret=True)
    snap = kernels.snapshot()
    assert snap["ssd_scan"] == "xla"
    assert "a mesh of 2 devices" in snap["reasons"]["ssd_scan"][0]
    kernels.reset()
    with jax.sharding.set_mesh(mesh):  # a mesh the call was not told of but can see
        _call(args, interpret=True)
    assert "a mesh of 2 devices" in kernels.snapshot()["reasons"]["ssd_scan"][0]


@pytest.mark.parametrize(
    "shape,chunk,reason",
    [
        (dict(S=64), 64, "chunk_size 64 is not a multiple of 128"),
        (dict(H=4, P=32, G=2), CHUNK, "head_dim 32 is neither 64 nor a multiple of 128"),
        (dict(H=3, P=64, G=3), CHUNK, "1 heads a group do not fill 128-lane tiles"),
        # what the v5e's compiler still refuses (VMEM): 16 tiles at a state of 2048
        (dict(S=128, H=16, P=128, G=1, N=2048), CHUNK, "is more than 8 MiB"),
    ],
    ids=["chunk", "head_dim", "odd_heads", "state_too_large"],
)
def test_at_an_unaligned_shape_the_site_falls_back_with_the_reason(fresh_record, shape, chunk,
                                                                   reason):
    args = _inputs(0, "harness", **shape)
    y, _ = _call(args, chunk_size=chunk, interpret=True)
    np.testing.assert_array_equal(y, mamba_chunk_scan_xla(*args[:6], chunk_size=chunk)[0])
    snap = kernels.snapshot()
    assert snap["ssd_scan"] == "xla"
    assert reason in snap["reasons"]["ssd_scan"][0]


def test_aligned_on_one_device_the_site_takes_the_kernels(fresh_record):
    args = _inputs(0, "harness")
    mesh = jax.make_mesh((1,), ("dp_shard",), devices=jax.devices()[:1])
    y, state = _call(args, mesh=mesh, interpret=True, initial_state=args[6],
                     output_final_state=True)
    snap = kernels.snapshot()
    assert snap["ssd_scan"] == "pallas" and snap["interpret"]
    assert "ssd_scan" not in snap["reasons"]
    with jax.default_matmul_precision("highest"):
        want_y, want_state = _xla(*args)
    np.testing.assert_allclose(y, want_y, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(state, want_state, atol=2e-4, rtol=1e-4)
