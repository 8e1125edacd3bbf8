"""The chunked SSD scan (``ops/mamba2.mamba_chunk_scan``) against the recurrence taken
token by token, under the published Mamba-2 initialisation: dt log-uniform in
[0.001, 0.1], A in [1, 16] (so a state lives for 0.6 to 1000 tokens), D ones. Ten chunks,
so that most of what a late token reads was written chunks ago: an inter-chunk state that
is dropped, decayed wrongly or handed on late fails here. The benchmark's cells cannot
hold this (their inits are the harness's three kinds, PERF.md section 4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.ops.mamba2 import mamba_chunk_scan

B, S, H, P, G, N, CHUNK = 2, 160, 8, 16, 2, 32, 16


def _published_init(seed: int):
    rng = np.random.RandomState(seed)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (B, S, H)))
    A = -rng.uniform(1.0, 16.0, (H,))
    x = rng.randn(B, S, H, P)
    Bm, Cm = rng.randn(B, S, G, N), rng.randn(B, S, G, N)
    return [jnp.asarray(a, jnp.float32) for a in (x, dt, A, Bm, Cm, np.ones(H))]


def _token_by_token(x, dt, A, Bm, Cm, D, state=None):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T; y_t = h_t C_t + D x_t."""
    r = H // G

    def token(h, args):
        x_t, dt_t, b_t, c_t = args
        b_h, c_h = jnp.repeat(b_t, r, axis=1), jnp.repeat(c_t, r, axis=1)
        h = h * jnp.exp(dt_t * A)[..., None, None] + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None]
        return h, jnp.sum(h * c_h[:, :, None], -1) + D[:, None] * x_t

    h0 = jnp.zeros((B, H, P, N), jnp.float32) if state is None else state
    h, y = jax.lax.scan(token, h0, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), h


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chunked_scan_is_the_recurrence_over_ten_chunks(seed):
    args = _published_init(seed)
    with jax.default_matmul_precision("highest"):
        want, want_state = _token_by_token(*args)
        got, got_state = mamba_chunk_scan(*args, chunk_size=CHUNK, output_final_state=True)
        # about half of what the last chunk reads of its state was written in earlier
        # chunks: a thousand times the tolerance, so a broken hand-over cannot pass
        alone, _ = mamba_chunk_scan(*(a[:, -CHUNK:] if a.ndim > 1 else a for a in args),
                                    chunk_size=CHUNK)
    carried = np.abs(np.asarray(want[:, -CHUNK:] - alone)).mean()
    read_out = np.abs(np.asarray(want[:, -CHUNK:] - args[0][:, -CHUNK:])).mean()  # less D x
    assert carried > 0.3 * read_out
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(got_state, want_state, atol=2e-4, rtol=1e-4)


def test_gradients_of_the_chunked_scan_are_the_recurrences():
    args = _published_init(3)
    weights = jnp.asarray(np.random.RandomState(4).randn(B, S, H, P), jnp.float32)

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a)[0] * weights), argnums=(0, 1, 2, 3, 4, 5))(*args)

    with jax.default_matmul_precision("highest"):
        want = through(_token_by_token)
        got = through(lambda *a: mamba_chunk_scan(*a, chunk_size=CHUNK))
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        scale = float(np.abs(np.asarray(w)).max())
        np.testing.assert_allclose(g, w, atol=2e-4 * scale, rtol=1e-3, err_msg=name)


def test_a_state_handed_in_continues_the_sequence():
    args = _published_init(5)
    half = S // 2
    with jax.default_matmul_precision("highest"):
        whole, _ = mamba_chunk_scan(*args, chunk_size=CHUNK)
        first = [a[:, :half] if a.ndim > 1 else a for a in args]
        second = [a[:, half:] if a.ndim > 1 else a for a in args]
        _, state = mamba_chunk_scan(*first, chunk_size=CHUNK, output_final_state=True)
        rest, _ = mamba_chunk_scan(*second, chunk_size=CHUNK, initial_state=state)
    np.testing.assert_allclose(rest, whole[:, half:], atol=2e-4, rtol=1e-4)
