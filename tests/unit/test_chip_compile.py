"""The main path's kernels, compiled for a TPU v5e that is described, not attached.

Interpret-mode tests show a kernel's arithmetic; only the chip's compiler shows
whether Mosaic accepts its tiles, its VMEM and its place in a mesh. The compiler
is installed here, so these compiles guard every later PR at no chip time
(`on-chip-measurement` guide, section 2). Nothing runs: no results, no times.

Rules this file keeps: the topology is described inside a module-scoped fixture
(never at import, in a ``skipif``, in ``parametrize`` arguments or in
``conftest.py``), compiles happen in the test's own process, and the persistent
compilation cache is off around them (a described-device entry cannot be read
back). Kernels only — a whole train step takes 15-90 s and belongs in a scratch
script before a chip run, not here.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds its lock
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    # conftest's float32 matmul precision is for CPU parity tests; Mosaic takes
    # bf16 operands at the default precision only ("Bad lhs type" otherwise)
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_default_matmul_precision", precision)
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from automodel_tpu.parallel.mesh import MeshContext

    return MeshContext(dp_shard=2, tp=2, world_size=4).build_mesh(topo.devices)


def _compile(fn, *args):
    """Optimized HLO of ``fn`` compiled for the described chip(s)."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "b,s,nq,nkv,d,kwargs",
    [
        (4, 2048, 32, 8, 64, {}),  # chip_smoke.py's shape
        (4, 2048, 32, 8, 64, {"segmented": True}),
        (4, 2048, 32, 8, 64, {"sliding_window": 512}),
        (2, 2048, 16, 8, 128, {}),
        (1, 4096, 16, 2, 256, {"segmented": True}),  # Qwen3-Next's full-attention layers
        (1, 4096, 20, 4, 128, {}),  # Falcon-H1's mixer: five query heads a kv head
    ],
    ids=["smoke_shape", "segment_ids", "sliding_window", "head_dim_128", "head_dim_256",
         "five_q_heads_a_kv_head"],
)
def test_flash_attention_fwd_bwd(one_chip, b, s, nq, nkv, d, kwargs):
    from automodel_tpu.ops.pallas.flash_attention import flash_attention

    kwargs = dict(kwargs)
    segmented = kwargs.pop("segmented", False)
    q = _sds((b, s, nq, d), BF16, one_chip)
    kv = _sds((b, s, nkv, d), BF16, one_chip)
    seg = _sds((b, s), jnp.int32, one_chip)

    def loss(q, k, v, seg):
        return flash_attention(
            q, k, v, segment_ids_q=seg if segmented else None, interpret=False, **kwargs
        ).astype(jnp.float32).sum()

    assert "tpu_custom_call" in _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, seg)


def test_grouped_matmul_fwd_bwd(one_chip):
    """Qwen3-30B-A3B expert width: (tokens*K, 2048) x (32 local experts, 2048, 1536)."""
    from automodel_tpu.ops.pallas.grouped_gemm import grouped_matmul

    x = _sds((16384, 2048), BF16, one_chip)
    w = _sds((32, 2048, 1536), BF16, one_chip)
    gs = _sds((32,), jnp.int32, one_chip)

    def loss(x, w, gs):
        return grouped_matmul(x, w, gs, interpret=False).astype(jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1)), x, w, gs)
    assert "tpu_custom_call" in hlo
    # the kernels' `name=` reaches the instruction name; with no scope around the call a
    # backward kernel's reads `%transpose_jvp_grouped_gemm_fwd__.1`: match by the part
    calls = re.findall(r"%([\w\-]+)\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    assert len(calls) == 2 and "grouped_gemm_fwd" in calls[0] + calls[1]
    assert "grouped_gemm_bwd_dw" in calls[0] + calls[1]


@pytest.mark.parametrize(
    "n,embed,vocab",
    [(8192, 2048, 128256), (8192, 2048, 151936), (8192, 2048, 262144),
     (8192, 4096, 151936), (8192, 8192, 128256), (37, 2048, 151936)],
    ids=["llama3", "qwen3_cell", "gemma", "embed_4096", "embed_8192", "short_batch"])
def test_fused_linear_ce_fwd_bwd(one_chip, n, embed, vocab):
    """(n, embed) hidden x (embed, vocab) unembedding — (8192, 2048, 151936) is the MoE
    cell's shape — at the tiles the picker gives: 128,256 and 151,936 are no multiple of
    them (last block computes its real columns only), 262,144 is. The wide models are the
    shapes nearest the VMEM budget (backward 128x1024 at 4096, 128x512 at 8192); 37 tokens
    are padded to one 64-row block. Two kernels: one forward, one backward."""
    from automodel_tpu.ops.losses import fused_linear_ce_tokens

    h = _sds((n, embed), BF16, one_chip)
    w = _sds((embed, vocab), BF16, one_chip)
    labels = _sds((n,), jnp.int32, one_chip)

    def loss(h, w, labels):
        z, gold = fused_linear_ce_tokens(h, w, labels, interpret=False)
        return (z - gold).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1)), h, w, labels)
    calls = re.findall(r"%([\w\-]+)\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    assert len(calls) == 2 and "linear_ce_fwd" in calls[0] + calls[1]
    assert "linear_ce_bwd" in calls[0] + calls[1]


@pytest.mark.parametrize("b,s,h,p,g,n", [(1, 4096, 128, 64, 8, 128), (1, 4096, 32, 128, 2, 256)],
                         ids=["two_heads_of_64_a_tile", "one_head_of_128_a_tile_state_256"])
def test_ssd_scan_fwd_bwd(one_chip, b, s, h, p, g, n):
    """The Mamba-2 scan at nemotron3super_pretrain_4k's shapes (one 4096-token row, 128
    heads x 64 in 8 groups, state 128) and at falconh1_pretrain_4k's (32 heads x 128 in 2
    groups, state 256: a head a tile, 16 tiles a grid step, a carried state of 2 MB);
    chunk 128, bf16 x, B, C and float32 dt. Two kernels: one forward (the gradient's own,
    which also writes the states), one backward."""
    from automodel_tpu.ops.pallas.ssd_scan import ssd_scan, ssd_scan_needs

    f32 = jnp.float32
    args = (_sds((b, s, h, p), BF16, one_chip), _sds((b, s, h), f32, one_chip),
            _sds((h,), f32, one_chip), _sds((b, s, g, n), BF16, one_chip),
            _sds((b, s, g, n), BF16, one_chip), _sds((h,), f32, one_chip))

    assert all(ok for ok, _ in ssd_scan_needs(args[0], args[3], 128))

    def loss(*a):
        return ssd_scan(*a, chunk_size=128)[0].astype(f32).sum()

    hlo = _compile(jax.grad(loss, argnums=tuple(range(6))), *args)
    calls = re.findall(r"%([\w\-]+)\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    assert len(calls) == 2 and "ssd_scan_fwd" in calls[0] + calls[1]
    assert "ssd_scan_bwd" in calls[0] + calls[1]
    assert "ssd_scan_fwd" in _compile(lambda *a: ssd_scan(*a, chunk_size=128)[0], *args)


def test_gated_delta_fwd_bwd(one_chip):
    """The gated delta rule at qwen3next_pretrain_4k's shapes: one 4096-token row, 16 key
    and 32 value heads of 128, bf16 q, k, v as the conv leaves them, float32 g and beta.
    Two kernels: one forward (the gradient's own, which also writes the states and the
    inverse), one backward."""
    from automodel_tpu.ops.pallas.gated_delta import gated_delta_rule

    b, s, hk, hv, d = 1, 4096, 16, 32, 128
    f32 = jnp.float32
    args = (_sds((b, s, hk, d), BF16, one_chip), _sds((b, s, hk, d), BF16, one_chip),
            _sds((b, s, hv, d), BF16, one_chip), _sds((b, s, hv), f32, one_chip),
            _sds((b, s, hv), f32, one_chip))

    def loss(*a):
        return gated_delta_rule(*a)[0].astype(f32).sum()

    hlo = _compile(jax.grad(loss, argnums=tuple(range(5))), *args)
    calls = re.findall(r"%([\w\-]+)\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    assert len(calls) == 2 and "gated_delta_fwd" in calls[0] + calls[1]
    assert "gated_delta_bwd" in calls[0] + calls[1]
    assert "gated_delta_fwd" in _compile(lambda *a: gated_delta_rule(*a)[0], *args)


def _ring_chunk_operands(sh, bn=32, bk=8, b=1, s=2048, d=64):
    from automodel_tpu.ops.pallas.flash_attention import LANES, SUBLANES

    q = _sds((bn, s, d), BF16, sh)
    kv = _sds((bk, s, d), BF16, sh)
    pos_q = _sds((b, s, LANES), jnp.int32, sh)
    pos_kv = _sds((b, SUBLANES, s), jnp.int32, sh)
    rows = _sds((bn, s, LANES), jnp.float32, sh)
    common = dict(scale=d**-0.5, causal=True, window=None, groups=bn // bk,
                  n_heads=bn // b, block_q=1024, block_k=1024, interpret=False)
    return q, kv, pos_q, pos_kv, rows, common


def test_ring_chunk_fwd_kernel(one_chip):
    """interpret=False said out loud: ring_attention_local derives it from the
    default backend, which here is the CPU."""
    from automodel_tpu.ops.pallas.ring_chunk import chunk_attention_fwd

    q, kv, pos_q, pos_kv, rows, common = _ring_chunk_operands(one_chip)
    acc = _sds(q.shape, jnp.float32, one_chip)

    def fwd(q, k, v, pq, pkv, acc, m, l):
        return chunk_attention_fwd(q, k, v, pq, pkv, None, None, acc, m, l, **common)

    hlo = _compile(fwd, q, kv, kv, pos_q, pos_kv, acc, rows, rows)
    assert "tpu_custom_call" in hlo and "%ring_attention_fwd." in hlo


def test_ring_chunk_bwd_kernel(one_chip):
    from automodel_tpu.ops.pallas.ring_chunk import chunk_attention_bwd

    q, kv, pos_q, pos_kv, rows, common = _ring_chunk_operands(one_chip)
    # what the ring hands its backward: alone the kernel compiles at 1024 too,
    # inside the ring's loop that is 17.2 MiB of 16 MiB scoped VMEM
    common["block_q"] = 512

    def bwd(q, k, v, pq, pkv, do, lse, delta):
        return chunk_attention_bwd(q, k, v, pq, pkv, None, None, do, lse, delta, **common)

    hlo = _compile(bwd, q, kv, kv, pos_q, pos_kv, q, rows, rows)
    assert "tpu_custom_call" in hlo and "%ring_attention_bwd." in hlo


@pytest.mark.parametrize("form", ["gathers", "scatter_adds"])
def test_dropless_experts_move_rows_without_a_scatter(one_chip, form):
    """Value and gradients of the branch a layer that holds all its experts takes, at
    the Qwen3-MoE cell's shapes (8,192 tokens x 2,048 bf16, top 8 of 128 experts of 768):
    the chip's compiler leaves no ``scatter`` under ``moe_dispatch`` / ``moe_combine``,
    and the compile row's reader counts none. The same reader on the form the tree had
    before PR 49 (a gather forward, scatter-adds as JAX transposes it; a sixteenth of the
    tokens, for the compile's seconds) counts the three it had: the combine forward, the
    dispatch backward, the weights' gradient."""
    from automodel_tpu.moe.config import MoEConfig
    from automodel_tpu.moe.experts import (grouped_experts_apply, init_expert_params,
                                           sort_held_rows, sorted_ragged_ffn)
    from automodel_tpu.observability.trace_analysis import moe_row_scatter_count

    cfg = MoEConfig(n_routed_experts=128, n_activated_experts=8, dim=2048, moe_inter_dim=768,
                    norm_topk_prob=True)
    T, K = 8192 if form == "gathers" else 512, 8
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), jax.eval_shape(
        lambda k: init_expert_params(cfg, k, BF16), jax.random.key(0)))
    x = _sds((T, cfg.dim), BF16, one_chip)
    weights = _sds((T, K), jnp.float32, one_chip)
    indices = _sds((T, K), jnp.int32, one_chip)

    def scatter_adds(cfg, params, x, weights, indices):
        rows, sorted_ids, group_sizes, _ = sort_held_rows(indices.reshape(-1), cfg.held_experts)
        with jax.named_scope("moe_dispatch"):
            xs = x[rows // K]
        out = sorted_ragged_ffn(cfg, params, xs, sorted_ids, group_sizes)
        with jax.named_scope("moe_combine"):
            w_sorted = weights.reshape(-1)[rows]
            y = jnp.zeros(x.shape, jnp.float32).at[rows // K].add(
                out.astype(jnp.float32) * w_sorted[:, None])
        return y.astype(x.dtype)

    apply = grouped_experts_apply if form == "gathers" else scatter_adds

    def loss(params, x, weights, indices):
        return apply(cfg, params, x, weights, indices).astype(jnp.float32).sum()

    hlo = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), params, x, weights, indices)
    assert "ragged-dot" in hlo  # the chip's grouped GEMM stands between the moves
    assert moe_row_scatter_count(hlo) == (0 if form == "gathers" else 3)


@pytest.mark.parametrize("ep", [4, 1], ids=["ep4", "one_device"])
def test_pallas_experts_in_the_a2a_region(topo, monkeypatch, ep):
    """``dispatcher: a2a`` + ``experts_backend: pallas`` as the recipe builds it:
    a six-axis mesh, the region manual over ``ep`` AND the size-1 axes
    (kernels.manual_axes) — JAX lowers no Mosaic kernel otherwise, not even on
    one device. Qwen3-30B-A3B expert widths, 8 experts per shard, top-2 of them
    (the XLA around the kernels is what takes the compile time); the
    varying-axes check stays on around the compiled kernel."""
    from automodel_tpu.moe import dispatch
    from automodel_tpu.moe.config import MoEConfig
    from automodel_tpu.moe.layers import init_moe_params
    from automodel_tpu.ops import kernels
    from automodel_tpu.parallel.mesh import MeshContext

    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    mesh = MeshContext(ep=ep, world_size=ep).build_mesh(topo.devices[:ep])
    cfg = MoEConfig(n_routed_experts=8 * ep, n_activated_experts=2, dim=2048,
                    moe_inter_dim=768)
    fn = dispatch.make_ep_moe_forward(cfg, mesh, experts_backend="pallas")
    params = jax.eval_shape(lambda k: init_moe_params(cfg, k, BF16), jax.random.key(0))
    on = lambda spec: lambda a: _sds(a.shape, a.dtype, NamedSharding(mesh, spec))
    params = {"gate": jax.tree.map(on(P()), params["gate"]),
              "experts": jax.tree.map(on(P("ep")), params["experts"])}
    x = _sds((ep, 256, cfg.dim), BF16, NamedSharding(mesh, P("ep")))

    def loss(params, x):
        return fn(params, x)[0].astype(jnp.float32).sum()

    if ep == 1:
        # JAX refuses at lowering, before the compiler: lowering is the test
        assert "tpu_custom_call" in jax.jit(loss).lower(params, x).as_text()
        return
    hlo = _compile(jax.grad(loss, argnums=(0, 1)), params, x)
    assert "tpu_custom_call" in hlo and "all-to-all" in hlo


def test_ring_attention_on_a_cp4_mesh(topo, monkeypatch):
    """``context_parallel: ring`` on the four-chip host: both ring_chunk kernels
    inside the cp region of ``make_ring_attention``, forward and backward, at
    2048 local rows of an 8192 sequence."""
    from automodel_tpu.ops import kernels
    from automodel_tpu.parallel import ring_attention
    from automodel_tpu.parallel.mesh import MeshContext

    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    mesh = MeshContext(cp=4, world_size=4).build_mesh(topo.devices)
    fn = ring_attention.make_ring_attention(mesh, impl="flash")
    sh = NamedSharding(mesh, P(None, "cp"))
    q = _sds((2, 8192, 32, 64), BF16, sh)
    kv = _sds((2, 8192, 8, 64), BF16, sh)
    pos = _sds((2, 8192), jnp.int32, sh)

    def loss(q, k, v, pos):
        return fn(q, k, v, pos, pos // 4096).astype(jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, pos)
    assert "tpu_custom_call" in hlo and "collective-permute" in hlo


def test_kernel_beside_a_gspmd_axis_is_refused_by_name(topo, monkeypatch):
    """ring beside dp_shard: the cp region leaves an axis of size 2 to GSPMD,
    JAX would refuse the kernel at lowering — the program says so first."""
    from automodel_tpu.ops import kernels
    from automodel_tpu.parallel import ring_attention
    from automodel_tpu.parallel.mesh import MeshContext

    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    mesh = MeshContext(dp_shard=2, cp=2, world_size=4).build_mesh(topo.devices)
    fn = ring_attention.make_ring_attention(mesh, impl="flash")
    sh = NamedSharding(mesh, P("dp_shard", "cp"))
    q = _sds((2, 4096, 32, 64), BF16, sh)
    kv = _sds((2, 4096, 8, 64), BF16, sh)
    with pytest.raises(kernels.KernelResolutionError, match=r"GSPMD still splits \{'dp_shard': 2\}"):
        _compile(fn, q, kv, kv, _sds((2, 4096), jnp.int32, sh))


def _mesh_operands(mesh4):
    from automodel_tpu.parallel.mesh import default_sharding_rules

    rules = default_sharding_rules().with_mesh(mesh4)
    sh = rules.sharding(("batch", None, "act_heads", None))
    q = _sds((4, 2048, 32, 64), BF16, sh)
    kv = _sds((4, 2048, 8, 64), BF16, sh)
    seg = _sds((4, 2048), jnp.int32, NamedSharding(mesh4, P("dp_shard")))
    return rules, q, kv, seg


def test_flash_in_shard_map_on_four_devices(mesh4, monkeypatch):
    """dp 2 x tp 2: the helper's shard_map hands each device's kernel its local
    batch and heads, with the varying-axes check on."""
    from automodel_tpu.ops import kernels
    from automodel_tpu.ops.attention import sharded_attention

    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    rules, q, kv, seg = _mesh_operands(mesh4)

    def loss(q, k, v, seg):
        return sharded_attention(
            q, k, v, rules=rules, backend="flash", segment_ids_q=seg,
            sliding_window=jnp.int32(512),
        ).astype(jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, seg)
    assert "tpu_custom_call" in hlo
    assert kernels.snapshot()["attention"] == "flash"


@pytest.mark.parametrize("policy,calls", [("none", ["fwd", "fwd", "bwd"]),
                                          ("mlp_attn_dots", ["fwd", "bwd"])])
def test_flash_residuals_cross_the_remat_boundary_out_of_the_shard_map(mesh4, monkeypatch,
                                                                       policy, calls):
    """The kernel's output and log-sum-exp are named inside the manual region; the rung
    that keeps them (``mlp_attn_dots``) leaves ONE forward call in the compiled step on
    four devices too, where ``none`` replays it."""
    from automodel_tpu.models.common.backend import BackendConfig
    from automodel_tpu.ops import kernels
    from automodel_tpu.ops.attention import sharded_attention

    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    rules, q, kv, seg = _mesh_operands(mesh4)
    layer = BackendConfig(remat_policy=policy).layer_remat(
        lambda q, k, v, seg: sharded_attention(
            q * 2, k, v, rules=rules, backend="flash", segment_ids_q=seg).astype(jnp.float32))

    def loss(q, k, v, seg):
        return jnp.tanh(layer(q, k, v, seg)).sum()

    hlo = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv, seg)
    found = re.findall(r"%flash_attention_(\w+?)\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    assert sorted(found) == sorted(calls)


def test_bare_kernel_on_sharded_operands_is_refused(mesh4, monkeypatch):
    """Why sharded_attention exists, and why every model call site hands it its
    rules: outside a shard_map JAX lowers no Mosaic kernel for sharded operands.
    Where the mesh is visible (``jax.sharding.set_mesh``) the program says so
    itself, by name."""
    from automodel_tpu.ops import kernels
    from automodel_tpu.ops.attention import dot_product_attention

    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    _, q, kv, seg = _mesh_operands(mesh4)

    def bare(q, k, v, seg):
        return dot_product_attention(q, k, v, segment_ids_q=seg, backend="flash")

    with pytest.raises(NotImplementedError, match="cannot be automatically partitioned"):
        _compile(bare, q, kv, kv, seg)
    with jax.sharding.set_mesh(mesh4), pytest.raises(
            kernels.KernelResolutionError, match="outside any manual region"):
        _compile(lambda *a: bare(*a), q, kv, kv, seg)


_STEP_CFG = """
seed: 7
output_dir: {out}
model:
  config:
    architectures: [{arch}]
    vocab_size: 2048
    hidden_size: 256
    intermediate_size: 512
    num_hidden_layers: {layers}
    num_attention_heads: 2
    num_key_value_heads: 1
    head_dim: 128
    max_position_embeddings: 1024
{model_extra}
  params_dtype: bfloat16
distributed: {distributed}
backend:
  dtype: bfloat16
  attention: flash
  attention_segments: false
{backend_extra}
loss:
  name: linear_ce
  impl: pallas
dataset:
  _target_: automodel_tpu.data.llm.mock.MockSFTDataset
  vocab_size: 2048
  seq_len: 1024
  num_samples: 16
  seed: 0
micro_batch_size: 1
seq_len: 1024
step_scheduler:
  grad_acc_steps: 1
  max_steps: 2
  num_epochs: 1
  handle_sigterm: false
optimizer:
  lr: 1.0e-3
checkpoint:
  enabled: false
"""

_DENSE = dict(arch="LlamaForCausalLM", model_extra="", backend_extra="", layers=2,
              distributed="{dp_shard: 1}")
# Nemotron-3's three layer kinds in one scan, a LatentMoE holding 4 of its router's 8 experts
_HYBRID = dict(arch="NemotronHForCausalLM", layers=5,
               model_extra="    hybrid_override_pattern: MEM*E\n    mamba_num_heads: 4\n"
                           "    mamba_head_dim: 64\n    ssm_state_size: 128\n    n_groups: 1\n"
                           "    chunk_size: 128\n    conv_kernel: 4\n    n_routed_experts: 4\n"
                           "    router_n_experts: 8\n    first_held_expert: 4\n"
                           "    num_experts_per_tok: 2\n    moe_intermediate_size: 256\n"
                           "    moe_latent_size: 128\n    moe_shared_expert_intermediate_size: 256\n"
                           "    routed_scaling_factor: 2.5\n    norm_topk_prob: true",
               backend_extra="  dispatcher: dense\n  experts_backend: ragged_dot\n  remat_policy: none",
               distributed="{dp_shard: 1}")
_MOE = dict(arch="Qwen3MoeForCausalLM", layers=2,
            model_extra="    moe_intermediate_size: 256\n    num_experts: 8\n"
                        "    num_experts_per_tok: 2\n    norm_topk_prob: true",
            backend_extra="  dispatcher: dense\n  experts_backend: ragged_dot",
            distributed="{dp_shard: 1}")


# Qwen3-Next's two layer kinds (L L L F), a MoE holding 4 of its router's 8 experts; the
# DeltaNet heads at the published width 128, two value heads a key head
_QWEN3_NEXT = dict(arch="Qwen3NextForCausalLM", layers=4,
                   model_extra="    linear_num_key_heads: 1\n    linear_num_value_heads: 2\n"
                               "    linear_key_head_dim: 128\n    linear_value_head_dim: 128\n"
                               "    linear_conv_kernel_dim: 4\n    full_attention_interval: 4\n"
                               "    partial_rotary_factor: 0.25\n    num_experts: 4\n"
                               "    router_n_experts: 8\n    first_held_expert: 0\n"
                               "    num_experts_per_tok: 2\n    moe_intermediate_size: 256\n"
                               "    shared_expert_intermediate_size: 256\n"
                               "    norm_topk_prob: true",
                   backend_extra="  dispatcher: dense\n  experts_backend: ragged_dot\n"
                                 "  remat_policy: none\n  scan_layers: false",
                   distributed="{dp_shard: 1}")


# Falcon-H1's block: a Mamba-2 mixer (a head of 128 a tile, two heads a group, state 256) and
# a rotary GQA mixer side by side, the muP scalars away from 1; the fused CE gets the
# logit multiplier through the hidden state
_FALCON_H1 = dict(arch="FalconH1ForCausalLM", layers=2,
                  model_extra="    mamba_d_ssm: 512\n    mamba_n_heads: 4\n    mamba_d_head: 128\n"
                              "    mamba_n_groups: 2\n    mamba_d_state: 256\n    mamba_d_conv: 4\n"
                              "    mamba_chunk_size: 128\n    rope_theta: 100000000000\n"
                              "    ssm_multipliers: [0.35, 0.25, 0.18, 0.5, 0.35]\n"
                              "    mlp_multipliers: [0.18, 0.011]\n    ssm_in_multiplier: 0.25\n"
                              "    ssm_out_multiplier: 0.088\n    attention_out_multiplier: 0.0375\n"
                              "    key_multiplier: 0.011\n    embedding_multiplier: 5.66\n"
                              "    lm_head_multiplier: 0.0078125",
                  backend_extra="  remat_policy: dots", distributed="{dp_shard: 1}")


@pytest.mark.parametrize(
    "family,kernel_names,labels",
    [
        (_DENSE,
         {"flash_attention_fwd", "flash_attention_bwd", "linear_ce_fwd", "linear_ce_bwd"},
         {"embed", "layer_stack", "attention", "mlp", "lm_head_loss", "optimizer"}),
        (_MOE,
         {"flash_attention_fwd", "flash_attention_bwd", "linear_ce_fwd", "linear_ce_bwd"},
         {"embed", "layer_stack", "attention", "moe", "moe_gate", "moe_dispatch", "moe_experts",
          "moe_combine", "lm_head_loss", "optimizer"}),
        (_HYBRID,
         {"flash_attention_fwd", "flash_attention_bwd", "linear_ce_fwd", "linear_ce_bwd",
          "ssd_scan_fwd", "ssd_scan_bwd"},
         {"embed", "layer_stack", "mamba", "mamba_proj", "mamba_ssd", "attention", "moe", "moe_gate",
          "moe_latent_proj", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared_experts",
          "lm_head_loss", "optimizer"}),
        (_QWEN3_NEXT,
         {"flash_attention_fwd", "flash_attention_bwd", "linear_ce_fwd", "linear_ce_bwd",
          "gated_delta_fwd", "gated_delta_bwd"},
         {"embed", "layer_stack", "delta_net", "delta_rule", "attention", "moe", "moe_gate",
          "moe_dispatch", "moe_experts", "moe_combine", "moe_shared_experts", "lm_head_loss",
          "optimizer"}),
        (_FALCON_H1,
         {"flash_attention_fwd", "flash_attention_bwd", "linear_ce_fwd", "linear_ce_bwd",
          "ssd_scan_fwd", "ssd_scan_bwd"},
         {"embed", "layer_stack", "mamba", "mamba_proj", "mamba_ssd", "attention", "mlp",
          "lm_head_loss", "optimizer"}),
    ],
    ids=["dense", "moe_ragged_dot", "nemotron_hybrid", "qwen3_next", "falcon_h1"],
)
def test_whole_step_carries_every_kernel_name_and_scope_label(
        topo, one_chip, monkeypatch, tmp_path, family, kernel_names, labels):
    """The recipe's own train step (set up on the CPU, lowered for the described chip)
    names what a device trace is read by: every Pallas kernel's instruction is
    ``<name>.<n>`` and every layer kind's label is on its operations' ``op_name`` paths
    (docs/observability.md "Device names"). Small widths at the kernels' tile sizes."""
    from automodel_tpu.config.loader import load_config
    from automodel_tpu.ops import kernels
    from automodel_tpu.parallel.mesh import MeshContext
    from automodel_tpu.recipes.llm import train_ft

    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    raw = {}
    jit_step = train_ft.jit_train_step
    monkeypatch.setattr(train_ft, "jit_train_step",
                        lambda step, *state: raw.setdefault("step", step) and jit_step(step, *state))

    class OneDevice(train_ft.TrainFinetuneRecipeForNextTokenPrediction):
        def _build_mesh(self, dist_cfg):
            ctx = MeshContext(**dist_cfg, world_size=1)
            return ctx, ctx.build_mesh(jax.devices()[:1])

    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(_STEP_CFG.format(out=tmp_path / "out", **family))
    recipe = OneDevice(load_config(cfg)).setup()
    stack = recipe._build_input_pipeline().get().stack
    abstract = lambda tree: jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), tree)  # noqa: E731
    hlo = _compile(raw["step"], abstract(recipe.train_params), abstract(recipe.opt_state),
                   abstract(stack))
    named = set(re.findall(r"%([a-z_]+)\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo))
    assert named == kernel_names
    on_paths = {label for op_name in re.findall(r'op_name="([^"]*)"', hlo)
                for label in re.findall(r"[A-Za-z_]\w*", op_name)}
    assert labels <= on_paths
