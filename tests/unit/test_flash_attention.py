"""Pallas flash attention vs the XLA reference — interpret mode on CPU gives exact
kernel semantics without hardware (the reference tests kernels the same way: CPU
parity vs a naive implementation, SURVEY.md §4)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.ops.attention import dot_product_attention
from automodel_tpu.ops.pallas.flash_attention import flash_attention


def _rand(key, *shape):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32)


def _ref(q, k, v, **kw):
    return dot_product_attention(q, k, v, backend="xla", **kw)


def _flash(q, k, v, **kw):
    return flash_attention(q, k, v, interpret=True, block_q=32, block_k=32, **kw)


class TestFlashForward:
    def test_causal_matches_xla(self):
        q, k, v = _rand(0, 2, 64, 4, 16), _rand(1, 2, 64, 4, 16), _rand(2, 2, 64, 4, 16)
        np.testing.assert_allclose(
            np.asarray(_flash(q, k, v, causal=True)),
            np.asarray(_ref(q, k, v, causal=True)),
            atol=2e-5,
        )

    def test_non_causal(self):
        q, k, v = _rand(3, 1, 32, 2, 8), _rand(4, 1, 32, 2, 8), _rand(5, 1, 32, 2, 8)
        np.testing.assert_allclose(
            np.asarray(_flash(q, k, v, causal=False)),
            np.asarray(_ref(q, k, v, causal=False)),
            atol=2e-5,
        )

    def test_gqa(self):
        q = _rand(6, 2, 64, 8, 16)
        k, v = _rand(7, 2, 64, 2, 16), _rand(8, 2, 64, 2, 16)
        np.testing.assert_allclose(
            np.asarray(_flash(q, k, v)),
            np.asarray(_ref(q, k, v)),
            atol=2e-5,
        )

    def test_segment_ids_packing(self):
        q, k, v = _rand(9, 2, 64, 4, 16), _rand(10, 2, 64, 4, 16), _rand(11, 2, 64, 4, 16)
        seg = jnp.concatenate(
            [jnp.full((2, 32), 1, jnp.int32), jnp.full((2, 32), 2, jnp.int32)], axis=1
        )
        got = _flash(q, k, v, segment_ids_q=seg)
        want = _ref(q, k, v, segment_ids_q=seg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_sliding_window(self):
        q, k, v = _rand(12, 1, 64, 2, 16), _rand(13, 1, 64, 2, 16), _rand(14, 1, 64, 2, 16)
        got = _flash(q, k, v, sliding_window=16)
        want = _ref(q, k, v, sliding_window=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_softmax_scale(self):
        q, k, v = _rand(15, 1, 32, 2, 8), _rand(16, 1, 32, 2, 8), _rand(17, 1, 32, 2, 8)
        got = _flash(q, k, v, softmax_scale=0.5)
        want = _ref(q, k, v, softmax_scale=0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_rejects_indivisible_seq(self):
        # 50 is not divisible by any block >= 8
        q = _rand(18, 1, 50, 2, 8)
        with pytest.raises(ValueError, match="divisible"):
            flash_attention(q, q, q, block_q=32, block_k=32, interpret=True)

    def test_block_fallback_divides_seq(self):
        # 48 % 32 != 0, but the picker falls back to 16 and matches xla
        q, k, v = _rand(19, 1, 48, 2, 8), _rand(20, 1, 48, 2, 8), _rand(21, 1, 48, 2, 8)
        out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
        ref = dot_product_attention(q, k, v, causal=True, backend="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


class TestFlashBackward:
    @pytest.mark.parametrize("bwd", ["fused", "split"])
    @pytest.mark.parametrize("case", ["causal", "gqa", "packed", "window"])
    def test_grads_match_xla(self, case, bwd, monkeypatch):
        # fused = single dq+dkv kernel (default when the kv scratch fits);
        # split = the two-kernel fallback that long-context shapes take
        import automodel_tpu.ops.pallas.flash_attention as fa

        monkeypatch.setenv("AUTOMODEL_FLASH_FUSED_BWD", "1" if bwd == "fused" else "0")
        before = fa._fused_bwd_traces
        self._check_grads(case)
        # guard against the VMEM gate silently taking the split path: the
        # "fused" parametrization must actually trace the fused kernel
        assert (fa._fused_bwd_traces > before) == (bwd == "fused")

    def _check_grads(self, case):
        kw = {}
        nh, nkv = 4, 4
        if case == "gqa":
            nkv = 2
        if case == "packed":
            kw["segment_ids_q"] = jnp.concatenate(
                [jnp.full((2, 32), 1, jnp.int32), jnp.full((2, 32), 2, jnp.int32)], axis=1
            )
        if case == "window":
            kw["sliding_window"] = 16
        q = _rand(20, 2, 64, nh, 16)
        k, v = _rand(21, 2, 64, nkv, 16), _rand(22, 2, 64, nkv, 16)

        def loss_flash(q, k, v):
            return (_flash(q, k, v, **kw) ** 2).sum()

        def loss_ref(q, k, v):
            return (_ref(q, k, v, **kw) ** 2).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gr), atol=5e-4,
                err_msg=f"d{name} mismatch in case {case}",
            )


class TestFusedVsSplitBackward:
    def test_everything_on_agreement(self, monkeypatch):
        """Fused and split backward agree bit-for-bit-ish with every kernel
        feature engaged at once (softcap + sinks + segments + GQA + causal)."""
        q = _rand(60, 2, 64, 4, 16)
        k, v = _rand(61, 2, 64, 2, 16), _rand(62, 2, 64, 2, 16)
        sinks = jnp.asarray([0.4, -0.2, 0.7, 0.0], jnp.float32)
        seg = jnp.concatenate(
            [jnp.full((2, 32), 1, jnp.int32), jnp.full((2, 32), 2, jnp.int32)], axis=1
        )

        def loss(q_, k_, v_, s_):
            return (_flash(q_, k_, v_, sinks=s_, segment_ids_q=seg,
                           logit_soft_cap=6.0) ** 2).sum()

        import automodel_tpu.ops.pallas.flash_attention as fa

        monkeypatch.setenv("AUTOMODEL_FLASH_FUSED_BWD", "1")
        before = fa._fused_bwd_traces
        g_fused = jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, sinks)
        assert fa._fused_bwd_traces > before, "fused path did not engage"
        monkeypatch.setenv("AUTOMODEL_FLASH_FUSED_BWD", "0")
        g_split = jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, sinks)
        for a, b, name in zip(g_fused, g_split, ["q", "k", "v", "sinks"]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5,
                err_msg=f"fused vs split d{name}",
            )


class TestSinksAndSoftCap:
    """gpt-oss sinks and gemma-style tanh capping inside the kernel (they
    previously forced the XLA fallback, ops/attention.py round-1)."""

    def test_soft_cap_matches_xla(self):
        q, k, v = _rand(20, 2, 64, 4, 16), _rand(21, 2, 64, 4, 16), _rand(22, 2, 64, 4, 16)
        got = _flash(q, k, v, logit_soft_cap=8.0)
        want = _ref(q, k, v, logit_soft_cap=8.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_soft_cap_grads(self):
        q, k, v = _rand(23, 1, 32, 2, 8), _rand(24, 1, 32, 2, 8), _rand(25, 1, 32, 2, 8)

        def loss(fn):
            return lambda q_, k_, v_: (fn(q_, k_, v_, logit_soft_cap=5.0) ** 2).sum()

        g_got = jax.grad(loss(_flash), argnums=(0, 1, 2))(q, k, v)
        g_want = jax.grad(loss(_ref), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_got, g_want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)

    def test_sinks_match_xla(self):
        q, k, v = _rand(26, 2, 64, 4, 16), _rand(27, 2, 64, 4, 16), _rand(28, 2, 64, 4, 16)
        sinks = jnp.asarray([0.5, -0.3, 1.2, 0.0], jnp.float32)
        got = _flash(q, k, v, sinks=sinks)
        want = _ref(q, k, v, sinks=sinks)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_sinks_grads_including_dsinks(self):
        q, k, v = _rand(29, 1, 32, 4, 8), _rand(30, 1, 32, 4, 8), _rand(31, 1, 32, 4, 8)
        sinks = jnp.asarray([0.2, -0.5, 0.8, 0.1], jnp.float32)

        def loss(fn):
            return lambda q_, k_, v_, s_: (fn(q_, k_, v_, sinks=s_) ** 2).sum()

        g_got = jax.grad(loss(_flash), argnums=(0, 1, 2, 3))(q, k, v, sinks)
        g_want = jax.grad(loss(_ref), argnums=(0, 1, 2, 3))(q, k, v, sinks)
        for a, b in zip(g_got, g_want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)

    def test_sinks_with_segments_and_gqa(self):
        q = _rand(32, 2, 64, 4, 16)
        k, v = _rand(33, 2, 64, 2, 16), _rand(34, 2, 64, 2, 16)
        sinks = jnp.asarray([0.5, -0.1, 0.3, 0.9], jnp.float32)
        seg = jnp.concatenate(
            [jnp.full((2, 32), 1, jnp.int32), jnp.full((2, 32), 2, jnp.int32)], axis=1
        )
        got = _flash(q, k, v, sinks=sinks, segment_ids_q=seg)
        want = _ref(q, k, v, sinks=sinks, segment_ids_q=seg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


class TestAttentionSegmentsFastPath:
    def test_unsegmented_matches_on_right_padded_real_tokens(self):
        """backend.attention_segments=False (bench fast path): with RIGHT-padded
        unpacked batches, causal masking alone isolates real tokens from the
        trailing pads, so real-token logits must match the segmented path
        exactly; pad rows are loss-masked and may diverge."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from automodel_tpu.models.common.backend import BackendConfig
        from automodel_tpu.models.llama.model import LlamaConfig, LlamaForCausalLM

        cfg = LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=32,
        )
        m_seg = LlamaForCausalLM(cfg, BackendConfig(dtype="float32"))
        m_fast = LlamaForCausalLM(cfg, BackendConfig(dtype="float32",
                                                     attention_segments=False))
        params = m_seg.init(jax.random.key(0), jnp.float32)
        rng = np.random.RandomState(0)
        ids = rng.randint(1, 64, (2, 16)).astype(np.int32)
        seg = np.ones((2, 16), np.int32)
        ids[1, 10:] = 0
        seg[1, 10:] = 0
        pos = np.broadcast_to(np.arange(16, dtype=np.int32), (2, 16))
        a = np.asarray(m_seg(params, ids, positions=pos, segment_ids=jnp.asarray(seg)))
        b = np.asarray(m_fast(params, ids, positions=pos, segment_ids=jnp.asarray(seg)))
        np.testing.assert_allclose(a[seg == 1], b[seg == 1], rtol=1e-6, atol=1e-6)

    def test_packing_with_fast_path_is_refused(self, tmp_path, cpu_devices):
        import textwrap

        import pytest

        from automodel_tpu.config.loader import load_config
        from automodel_tpu.recipes.llm.train_ft import (
            TrainFinetuneRecipeForNextTokenPrediction,
        )

        cfg_text = f"""
        seed: 7
        output_dir: {tmp_path}/out
        model:
          config:
            architectures: [LlamaForCausalLM]
            vocab_size: 128
            hidden_size: 32
            intermediate_size: 64
            num_hidden_layers: 2
            num_attention_heads: 4
            num_key_value_heads: 2
            max_position_embeddings: 128
        distributed: {{dp_shard: 8}}
        backend: {{dtype: float32, attention_segments: false}}
        packed_sequence: {{packed_sequence_size: 64}}
        dataset:
          _target_: automodel_tpu.data.llm.mock.MockSFTDataset
          vocab_size: 128
          seq_len: 32
          num_samples: 64
          seed: 0
        micro_batch_size: 8
        seq_len: 32
        step_scheduler: {{grad_acc_steps: 1, max_steps: 1, handle_sigterm: false}}
        optimizer: {{lr: 1.0e-3}}
        checkpoint: {{enabled: false}}
        """
        p = tmp_path / "cfg.yaml"
        p.write_text(textwrap.dedent(cfg_text))
        r = TrainFinetuneRecipeForNextTokenPrediction(load_config(p))
        with pytest.raises(ValueError, match="attention_segments"):
            r.setup()


def test_flash_off_tpu_is_recorded_and_refused_with_the_reason():
    """``backend: flash`` off the TPU runs the einsum — no longer quietly: the
    resolution lands in ops.kernels (and from there in the run header), and
    whoever needs the compiled kernel (chip_smoke.py) is refused with the reason."""
    from automodel_tpu.ops import kernels
    from automodel_tpu.ops.attention import dot_product_attention

    kernels.reset()
    q = jnp.ones((1, 16, 2, 8), jnp.float32)
    out = dot_product_attention(q, q, q, backend="flash")
    ref = dot_product_attention(q, q, q, backend="xla")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    snap = kernels.snapshot()
    assert snap["attention"] == "xla" and snap["interpret"] is False
    (why,) = snap["reasons"]["attention"]
    assert "flash unusable" in why and "default backend is cpu" in why
    with pytest.raises(kernels.KernelResolutionError, match="default backend is cpu"):
        kernels.require_compiled(snap, attention="flash")
    # the kernel's own logic, interpreted, is not a compiled kernel either
    kernels.reset()
    dot_product_attention(q, q, q, backend="flash_interpret")
    assert kernels.snapshot()["attention"] == "flash"
    with pytest.raises(kernels.KernelResolutionError, match="interpret mode"):
        kernels.require_compiled(kernels.snapshot(), attention="flash")
    kernels.reset()


@pytest.mark.parametrize(
    "shape,named,fully_manual",
    [({"ep": 8}, ("ep",), True), ({"cp": 8}, ("cp",), True), ({"pp": 8}, ("pp",), True),
     ({"pp": 2, "ep": 4}, ("pp", "ep"), True),
     ({"dp_shard": 2, "ep": 4}, ("ep",), False), ({"pp": 2, "dp_shard": 2, "tp": 2}, ("pp",), False)],
    ids=["ep8", "cp8", "pp8", "pp2xep4", "dp2xep4", "pp2xdp2xtp2"],
)
def test_manual_axes_and_the_region_a_compiled_kernel_needs(shape, named, fully_manual):
    """JAX lowers a Mosaic kernel only in a region manual over EVERY mesh axis,
    size-1 ones included. ``manual_axes`` adds those to a region's own axes, so
    an ep-, cp- or pp-only mesh keeps its kernels; beside an axis GSPMD still
    splits, ``check_manual_region`` refuses by name (what the chip's lowering
    says is pinned in test_chip_compile.py)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from automodel_tpu.ops import kernels
    from automodel_tpu.parallel.mesh import MeshContext

    mesh = MeshContext(**shape, world_size=8).build_mesh(jax.devices())
    axes = kernels.manual_axes(mesh, *named)
    assert set(named) <= axes
    assert (axes == set(mesh.axis_names)) == fully_manual
    assert all(mesh.shape[a] == 1 for a in axes - set(named))

    def body(x):
        kernels.check_manual_region("a kernel")
        return x

    region = jax.shard_map(body, mesh=mesh, in_specs=P(named), out_specs=P(named),
                           axis_names=axes)
    x = jnp.zeros((8, 4))
    if fully_manual:
        jax.jit(region)(x)
    else:
        with pytest.raises(kernels.KernelResolutionError, match="GSPMD still splits"):
            jax.jit(region)(x)
