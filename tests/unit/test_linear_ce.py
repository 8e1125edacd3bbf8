"""Pallas fused linear-CE: value + gradient parity vs the reference XLA path,
vocab-shard partial combine, and recipe-path integration (interpret mode)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.ops.losses import (
    fused_linear_ce_tokens,
    linear_cross_entropy,
    masked_cross_entropy,
)

N, E, V = 48, 128, 512
# a vocabulary no candidate tile divides: the picker gives 1024-column tiles, so the
# kernels' last block computes 128 real columns of its 1024 (1152 = 1024 + 128)
V_TAIL = 1152


def _data(seed=0, ignore_frac=0.25, v=V):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(N, E).astype(np.float32) * 0.3)
    w = jnp.asarray(rng.randn(E, v).astype(np.float32) * 0.1)
    labels = rng.randint(0, v, (N,))
    labels[rng.rand(N) < ignore_frac] = -100
    return h, w, jnp.asarray(labels, jnp.int32)


@pytest.fixture(params=[V, V_TAIL], ids=["vocab_divides", "vocab_tail_block"])
def vocab(request):
    from automodel_tpu.ops.pallas.linear_ce import pick_blocks, pick_bwd_blocks

    v = request.param
    for blocks in (pick_blocks(E, v, N), pick_bwd_blocks(E, v, N)):
        assert (v % blocks[1] != 0) == (v == V_TAIL)  # the case is what its id says
    return v


class TestFusedLinearCE:
    def test_forward_matches_masked_ce(self, vocab):
        h, w, labels = _data(v=vocab)
        logits = h @ w
        ref = masked_cross_entropy(logits, labels, num_label_tokens=32)
        got = linear_cross_entropy(h, w, labels, num_label_tokens=32, impl="pallas")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("filter_eps", [1e-7, None], ids=["filtered", "exact"])
    def test_grads_match(self, vocab, filter_eps):
        """Loss gradients against autodiff through materialised logits; with
        ``filter_eps=None`` no block is skipped and the same tolerances hold."""
        h, w, labels = _data(seed=1, v=vocab)

        def ref_loss(h_, w_):
            return masked_cross_entropy(h_ @ w_, labels, num_label_tokens=30)

        def fused_loss(h_, w_):
            return linear_cross_entropy(
                h_, w_, labels, num_label_tokens=30, impl="pallas", filter_eps=filter_eps)

        ref_dh, ref_dw = jax.grad(ref_loss, argnums=(0, 1))(h, w)
        got_dh, got_dw = jax.grad(fused_loss, argnums=(0, 1))(h, w)
        np.testing.assert_allclose(np.asarray(got_dh), np.asarray(ref_dh), rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(got_dw), np.asarray(ref_dw), rtol=2e-3, atol=2e-4)

    def test_token_padding(self, vocab):
        """N not divisible by block_n: padded rows must not leak into the loss or
        the gradients (with a tail vocabulary block in the same call too)."""
        h, w, labels = _data(seed=2, v=vocab)
        h_odd, labels_odd = h[:37], labels[:37]

        def ref_loss(h_, w_):
            return masked_cross_entropy(h_ @ w_, labels_odd, num_label_tokens=20)

        def fused_loss(h_, w_):
            return linear_cross_entropy(h_, w_, labels_odd, num_label_tokens=20, impl="pallas")

        np.testing.assert_allclose(
            np.asarray(fused_loss(h_odd, w)), np.asarray(ref_loss(h_odd, w)), rtol=1e-3, atol=1e-3)
        ref_dh, ref_dw = jax.grad(ref_loss, argnums=(0, 1))(h_odd, w)
        got_dh, got_dw = jax.grad(fused_loss, argnums=(0, 1))(h_odd, w)
        assert got_dh.shape == h_odd.shape
        np.testing.assert_allclose(np.asarray(got_dh), np.asarray(ref_dh), rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(got_dw), np.asarray(ref_dw), rtol=2e-3, atol=2e-4)

    @pytest.mark.parametrize("split", [V // 2, 1152], ids=["even_shards", "tail_block_shard"])
    def test_vocab_shard_combine(self, split):
        """Two vocab shards with localized labels reproduce the global loss via
        logsumexp-combine of z and sum of gold (the second case's first shard is
        V_TAIL wide, so its kernels end on a partly real block)."""
        full = V if split == V // 2 else 1152 + 512
        h, w, labels = _data(seed=3, v=full)
        half = split
        z0, g0 = fused_linear_ce_tokens(h, w[:, :half], labels, vocab_offset=0)
        z1, g1 = fused_linear_ce_tokens(h, w[:, half:], labels, vocab_offset=half)
        z = jnp.logaddexp(z0, z1)
        gold = g0 + g1
        valid = labels != -100
        got = jnp.where(valid, z - gold, 0.0).sum() / 25.0
        ref = masked_cross_entropy(h @ w, labels, num_label_tokens=25)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-3, atol=1e-3)

    def test_filtered_blocks_are_skipped_between_computed_ones(self):
        """Vocabulary blocks whose whole softmax tile is below ``filter_eps`` run no
        step of the backward (their dW is exactly zero, not e^-50) while the blocks
        before and after them still add into the same dH rows."""
        from automodel_tpu.ops.pallas.linear_ce import pick_bwd_blocks

        v = 4 * 2048
        assert pick_bwd_blocks(E, v, N)[1] == 2048
        h, w, labels = _data(seed=8, v=v)
        # 64 rows, a whole token block: a padded (all-zero) row has a flat softmax,
        # 1/V everywhere, which keeps every block of its token block significant
        h, labels = jnp.concatenate([h, h[:16] * 0.5]), jnp.concatenate([labels, labels[:16]])
        dead = slice(2048, 6144)  # backward blocks 1 and 2 of 4
        h = h.at[:, 0].set(5.0)
        w = w.at[0, dead].set(-10.0)  # logits about -50 there
        labels = jnp.where((labels >= dead.start) & (labels < dead.stop), 7, labels)

        def ref_loss(h_, w_):
            return masked_cross_entropy(h_ @ w_, labels, num_label_tokens=30)

        def fused_loss(h_, w_):
            return linear_cross_entropy(h_, w_, labels, num_label_tokens=30, impl="pallas")

        ref_dh, ref_dw = jax.grad(ref_loss, argnums=(0, 1))(h, w)
        got_dh, got_dw = jax.grad(fused_loss, argnums=(0, 1))(h, w)
        assert float(jnp.abs(got_dw[:, dead]).max()) == 0.0 < float(jnp.abs(ref_dw[:, dead]).max())
        np.testing.assert_allclose(np.asarray(got_dh), np.asarray(ref_dh), rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(got_dw), np.asarray(ref_dw), rtol=2e-3, atol=2e-4)

    def test_bf16_inputs(self):
        h, w, labels = _data(seed=4)
        hb, wb = h.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        ref = masked_cross_entropy(
            hb.astype(jnp.float32) @ wb.astype(jnp.float32), labels, num_label_tokens=30
        )
        got = linear_cross_entropy(hb, wb, labels, num_label_tokens=30, impl="pallas")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-2, atol=2e-2)
        dh = jax.grad(
            lambda h_: linear_cross_entropy(h_, wb, labels, num_label_tokens=30, impl="pallas")
        )(hb)
        assert dh.dtype == jnp.bfloat16

    def test_all_ignored_block(self):
        """A fully-ignored token block contributes exactly zero."""
        h, w, labels = _data(seed=5)
        labels = jnp.full_like(labels, -100)
        got = linear_cross_entropy(h, w, labels, num_label_tokens=1, impl="pallas")
        assert float(got) == 0.0
        dh = jax.grad(
            lambda h_: linear_cross_entropy(h_, w, labels, num_label_tokens=1, impl="pallas")
        )(h)
        assert float(jnp.abs(dh).max()) == 0.0

    def test_xla_fallback_unsupported_vocab(self):
        """Vocab not divisible by 128 silently uses the XLA scan path."""
        rng = np.random.RandomState(6)
        h = jnp.asarray(rng.randn(16, 128).astype(np.float32))
        w = jnp.asarray(rng.randn(128, 200).astype(np.float32))
        labels = jnp.asarray(rng.randint(0, 200, (16,)), jnp.int32)
        ref = masked_cross_entropy(h @ w, labels, num_label_tokens=16)
        got = linear_cross_entropy(h, w, labels, num_label_tokens=16, impl="pallas")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


class TestBwdFeasibility:
    def test_supported_requires_backward_tiling(self):
        """Shapes whose forward tiles but whose backward accumulator blows the
        VMEM budget must NOT pass the supported check (advisor r2: embed 12288
        with 128k vocab picked (64,128) forward then crashed tracing grad)."""
        from automodel_tpu.ops.losses import pallas_linear_ce_supported
        from automodel_tpu.ops.pallas.linear_ce import pick_blocks, pick_bwd_blocks

        e, v = 12288, 131072
        assert pick_blocks(e, v) is not None  # forward alone tiles...
        assert pick_bwd_blocks(e, v) is None  # ...backward cannot
        assert not pallas_linear_ce_supported(e, v)

    def test_bwd_xla_fallback_matches_autodiff(self):
        """The blockwise-XLA backward fallback gives the exact logsumexp grads."""
        from automodel_tpu.ops.pallas.linear_ce import _bwd_xla_fallback

        rng = np.random.RandomState(7)
        h = jnp.asarray(rng.randn(16, 64).astype(np.float32) * 0.3)
        w = jnp.asarray(rng.randn(64, 256).astype(np.float32) * 0.1)
        dz = jnp.asarray(rng.randn(16).astype(np.float32))

        def ref(h, w):
            return (jax.nn.logsumexp(h @ w, axis=-1) * dz).sum()

        dh_ref, dw_ref = jax.grad(ref, argnums=(0, 1))(h, w)
        z = jax.nn.logsumexp(h @ w, axis=-1)
        dh, dw = _bwd_xla_fallback(h, w, z, dz, block_v=128)
        np.testing.assert_allclose(np.asarray(dh), np.asarray(dh_ref), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref), rtol=1e-5, atol=1e-5)


class TestTilePicker:
    @pytest.mark.parametrize("v", [151936, 129280, 128256, 262144],
                             ids=["qwen", "deepseek", "llama3", "gemma"])
    def test_vocabulary_tiles_do_not_follow_divisors(self, v):
        """151,936 = 128 x 1187 (prime) and 129,280 = 256 x 505 got 128- and
        256-column tiles while tiles had to divide V; now every vocabulary gets
        the same wide tile at a given width, forward and backward."""
        from automodel_tpu.ops.pallas.linear_ce import pick_blocks, pick_bwd_blocks

        fwd, bwd = pick_blocks(2048, v), pick_bwd_blocks(2048, v)
        assert fwd[1] >= 512 and bwd[1] >= 512
        assert (fwd, bwd) == (pick_blocks(2048, 262144), pick_bwd_blocks(2048, 262144))

    @pytest.mark.parametrize(
        "e,v,fwd,bwd",
        [(1536, 151936, (512, 2048), (256, 2048)), (2048, 151936, (512, 2048), (256, 2048)),
         (3072, 128256, (256, 2048), (256, 1024)), (4096, 151936, (256, 2048), (128, 1024)),
         (8192, 128256, (256, 1024), (128, 512))],
        ids=["e1536", "e2048_cell", "e3072", "e4096", "e8192"])
    def test_wide_models_get_the_tiles_the_chip_ran_fastest(self, e, v, fwd, bwd):
        """The tile that was fastest of those timed on a v5e at each width (the
        times are in ``_pick``'s docstring): widest vocabulary tile that fits, then
        the tallest token tile whose h tile (forward) or dH block (backward) stays
        under the size at which the chip slowed down."""
        from automodel_tpu.ops.pallas.linear_ce import pick_blocks, pick_bwd_blocks

        assert (pick_blocks(e, v), pick_bwd_blocks(e, v)) == (fwd, bwd)

    @pytest.mark.parametrize("n", [16, 37, 8192])
    def test_a_shape_supported_at_the_default_batch_tiles_at_any(self, n):
        """``pallas_linear_ce_supported`` asks the picker without a token count and
        ``fused_linear_ce_tokens`` with the real one: fewer tokens only admit
        shorter tiles, so what the check allows never comes back None."""
        from automodel_tpu.ops.pallas.linear_ce import pick_blocks, pick_bwd_blocks

        for e in (128, 896, 2048, 2560, 4096, 5120, 8192, 12288, 16384):
            for v in (512, 2176, 128256, 151936, 262144):
                for pick in (pick_blocks, pick_bwd_blocks):
                    if pick(e, v) is not None:
                        assert pick(e, v, n) is not None, (pick.__name__, e, v, n)

    def test_tiles_follow_the_vmem_the_device_has(self, monkeypatch):
        """On a core with 16 MiB of VMEM (a v4) the limit asked of Mosaic stays under
        its default, the forward gets a smaller tile and the backward none: the
        recipe then takes the XLA path and does not fail at compile."""
        import types

        from automodel_tpu.ops.losses import pallas_linear_ce_supported
        from automodel_tpu.ops.pallas import linear_ce

        assert linear_ce._vmem_limit() == 80 * 2**20  # no TPU here: the v5e's share
        monkeypatch.setattr(linear_ce.pltpu, "get_tpu_info",
                            lambda: types.SimpleNamespace(vmem_capacity_bytes=16 * 2**20))
        assert linear_ce._vmem_limit() == 10 * 2**20
        assert linear_ce.pick_blocks(2048, 151936) == (256, 512)
        assert linear_ce.pick_bwd_blocks(2048, 151936) is None
        assert not pallas_linear_ce_supported(2048, 151936)

    def test_small_batches_get_tiles_no_taller_than_they_are(self):
        from automodel_tpu.ops.pallas.linear_ce import pick_blocks, pick_bwd_blocks

        assert pick_blocks(128, 512, 37)[0] == 64 and pick_bwd_blocks(128, 512, 37)[0] == 64
        assert pick_blocks(100, 512) is None and pick_blocks(128, 200) is None  # not lane-aligned

    def test_run_header_names_the_tiles_and_the_masked_columns(self):
        from automodel_tpu.ops import kernels

        kernels.reset()
        h, w, labels = _data(v=V_TAIL)
        fused_linear_ce_tokens(h, w, labels)
        header = kernels.snapshot()
        assert header["loss_tiles"] == "fwd 64x1024 masked 896 bwd 64x1024 masked 896"
        kernels.require_compiled({**header, "interpret": False, "loss": "pallas"}, loss="pallas")
        kernels.reset()

    def test_filter_table_is_a_superset_across_tile_widths(self):
        """A backward block is kept when ANY forward block overlapping its columns is
        significant, whatever the two widths are (neither has to divide the other)."""
        from automodel_tpu.ops.pallas.linear_ce import _block_significance

        # forward blocks of 384 columns over V = 1152 (3 blocks), backward of 512 (3 blocks,
        # the last one 128 real columns); 2 token blocks of 8 rows
        bmax = jnp.full((3, 1, 16), -50.0).at[1, 0, 3].set(0.0)  # row 3 significant in fwd block 1
        z = jnp.zeros((16,))
        sig = _block_significance(bmax, z, 384, 2, 8, 3, 512, float(np.log(1e-7)))
        # fwd block 1 = columns [384, 768) overlaps bwd blocks 0 ([0, 512)) and 1 ([512, 1024))
        np.testing.assert_array_equal(np.asarray(sig), [[1, 1, 0], [0, 0, 0]])
        assert np.asarray(_block_significance(bmax, z, 384, 2, 8, 3, 512, None)).all()
