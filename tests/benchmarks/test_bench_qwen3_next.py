"""The Qwen3-Next family's benchmark files against the program and against the published
code, on the CPU at small sizes and seeded random weights: ``reference/qwen3_next.py``
(token-by-token delta rule, a loop over the held experts) against ``models/qwen3_next``
(chunked rule with a triangular solve, sorted grouped GEMMs) for each layer kind alone and
for the cell's ``L L L F L L L F`` pattern, loss and per-leaf gradient norms; the shares of
an expert-parallel layer add up to the uncut layer; the reference's forward against
``transformers.Qwen3NextForCausalLM``; the adapter's tree against the program's; the counts
the cell's ``flops_per_token`` and roofline readers are built from.

The cell is rehearsed with the accepted cells in ``test_bench_rehearse.py`` and its control
in ``test_bench_control.py``; here the traced rehearsal and two planted faults.

``test_bench_nemotron_h.py``'s first test looks its entries up as the LAST of their lists
in ``BENCHMARK.json``, so it fails from the first PR on that appends one (this one); the
file is under the benchmark's ``paths`` and only a ``benchmark`` PR may edit it (PERF.md
section 7). ``test_the_cells_entries_keep_the_contract`` below asks the same of both
hybrid cells' entries, found by name."""

import importlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.qwen3_next.model import Qwen3NextConfig, Qwen3NextForCausalLM
from automodel_tpu.moe.layers import moe_forward
from automodel_tpu.ops.gated_delta import chunk_gated_delta_rule, l2norm
from benchmarks.adapters import qwen3_next as adapter
from benchmarks.harness import flops, optstate, spec, weights
from benchmarks.reference import qwen3_next as reference
from benchmarks.reference.train import _collect

from tests.benchmarks.rehearsal import rehearse

CELL = "qwen3next_pretrain_4k"
CONFIG = "qwen3-next-80b-a3b-p8-ep16"
NEW_METRICS = ("delta_net_device_ms", "gated_delta_roofline")
JOINED_METRICS = ("moe_device_ms", "expert_gemm_roofline", "moe_held_rows_share")
# the catalog row's `config` (guide `model-configs`, architectures.jsonl, source_url below)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
SOURCE = "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"


@pytest.mark.parametrize("config_name,cell_name,new_metrics,published", [
    (CONFIG, CELL, NEW_METRICS, PUBLISHED),
    ("nemotron-3-super-120b-a12b-p11-ep32", "nemotron3super_pretrain_4k",
     ("mamba_device_ms", "ssd_scan_roofline", "moe_latent_proj_device_ms", "moe_held_rows_share"),
     None),
], ids=["qwen3_next", "nemotron_h"])
def test_the_cells_entries_keep_the_contract(config_name, cell_name, new_metrics, published):
    """What ``test_bench_units.py`` asks of every entry, asked of a hybrid cell's, found by
    name: one key stands in the way there, ``vocab_size`` in ``reduced``, which the guide
    lists when the vocabulary is sliced. No width differs from the source's."""
    if published is None:
        from tests.unit.test_nemotron3_config import PUBLISHED as published
    bench = spec.benchmark_json()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    config = next(c for c in bench["configs"] if c["name"] == config_name)
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    per_layer = [m for m in bench["per_layer"] if m["name"] in new_metrics]
    assert cell["config"] == config_name and cell["chips"] == 1 and cell["traffic"] == "pretrain_4k"
    assert [m["name"] for m in per_layer] == list(new_metrics)
    for entry in (config, cell, *per_layer):
        assert name.match(entry["name"])
        assert all(1 <= len(entry[k]) <= 200 for k in ("why", "layer", "source") if k in entry)
    assert [k for k in config["reduced"] if k.endswith(("_dim", "_rank", "_size"))] == ["vocab_size"]
    with open(os.path.join(spec.ROOT, config["file"])) as f:
        file = json.load(f)
    assert file["reduced"] == config["reduced"] == list(file["published"])
    assert {k for k, v in published.items() if file.get(k) != v} == set(config["reduced"])
    assert all(file["published"][k] == published[k] for k in config["reduced"])
    moved = {m["name"] for m in bench["end_to_end"]}
    for metric in per_layer:
        assert cell_name in metric["workloads"] and metric["moves"] in moved
        assert callable(importlib.import_module("benchmarks.metrics." + metric["name"]).read)
    reported = {m["name"] for m in spec.Cell(cell_name).per_layer}
    assert reported >= set(new_metrics) | set(JOINED_METRICS)
    assert "mlp_device_ms" not in reported and "linear_ce_roofline" not in reported


def test_the_new_entries_name_their_cell_and_state_the_cut():
    """Found by name, not by place: the next PR appends its entries behind these."""
    bench = spec.benchmark_json()
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["source"] == SOURCE
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for metric in bench["per_layer"]:
        if metric["name"] in NEW_METRICS:
            assert metric["workloads"] == [CELL]
        if metric["name"] in JOINED_METRICS:
            assert metric["workloads"].count(CELL) == 1
    file = spec.Cell(CELL).config
    assert file["router_n_experts"] == 512 and file["first_held_expert"] == 0
    assert "16 chips" in file["deployment"] and "mtp" in file["assumed"]


def test_a_rehearsed_run_of_the_cell_is_correct_and_counts_the_held_rows(capsys, tmp_path):
    result, lines, failed = rehearse(capsys, "--workload", CELL, "--seed", str(2**31 + 7),
                                     "--trace", "1", "--out", str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0, failed
    # the program's counter is read on a CPU too; no device reader finds a trace there
    assert set(result["metrics"]) == {"data_wait_ms", "compiles_in_window", "step_hbm_gib",
                                      "moe_held_rows_share"}
    assert 0 < result["metrics"]["moe_held_rows_share"]["value"] < 100
    compared = " ".join(line for line in lines if "worst:" in line)
    assert "linear_layers." in compared or "full_layers." in compared


def test_a_step_that_returns_its_parameters_unchanged_is_not_correct(capsys, tmp_path, monkeypatch):
    from automodel_tpu.recipes.llm import train_ft

    real = train_ft.make_train_step

    def broken(*args, **kwargs):
        step = real(*args, **kwargs)

        def keeps_its_parameters(params, opt_state, *rest):
            _, new_state, metrics = step(params, opt_state, *rest)
            return params, new_state, metrics

        return keeps_its_parameters

    monkeypatch.setattr(train_ft, "make_train_step", broken)
    result, _, failed = rehearse(capsys, "--workload", CELL, "--seed", "22", "--out", str(tmp_path))
    assert result["correct"] is False
    assert "parameter_change_norm_gap_after_2" in failed


def test_half_of_each_row_left_out_is_not_correct(capsys, tmp_path, monkeypatch):
    from benchmarks.generators import token_stream

    real = token_stream.Dataset.__iter__

    def half_rows(self):
        for example in real(self):
            example["prompt_len"] = self.seq_len // 2  # the first half carries no loss
            yield example

    monkeypatch.setattr(token_stream.Dataset, "__iter__", half_rows)
    result, _, failed = rehearse(capsys, "--workload", CELL, "--seed", "23", "--out", str(tmp_path))
    assert result["correct"] is False
    assert failed & {"loss_step_1_gap", "first_gradient_norm_gap"}


def _model_dict(kinds: str, **kw) -> dict:
    """The cell's tiny model with the layer kinds spelled out (``L`` DeltaNet, ``F`` full)."""
    types = ["full_attention" if c == "F" else "linear_attention" for c in kinds]
    m = dict(spec.Cell(CELL, tiny=True).model, layer_types=types, num_hidden_layers=len(kinds))
    m.update(kw)
    return m


def _spread(blocks: dict, seed: int) -> dict:
    """Spread the constant leaves too, or a wrong norm, bias or decay would not show."""
    key = jax.random.key(seed + 1)
    for i, (name, leaves) in enumerate(blocks.items()):
        for j, leaf in enumerate(sorted(leaves)):
            if leaves[leaf].ndim == 1:
                noise = jax.random.normal(jax.random.fold_in(key, 100 * i + j), leaves[leaf].shape)
                leaves[leaf] = leaves[leaf] + 0.3 * noise
    return blocks


def _both_sides(m: dict, seed: int, rows: int = 2, seq: int = 64):
    """Loss and per-leaf sums of squared gradients: (program, reference), float32."""
    groups = reference.layer_groups(m)
    blocks = _spread(weights.make_blocks(reference, m, seed, "float32"), seed)
    rng = np.random.RandomState(seed)
    ids = jnp.asarray(rng.randint(0, m["vocab_size"], (rows, seq)))
    labels = jnp.asarray(rng.randint(0, m["vocab_size"], (rows, seq)))

    squares = {}
    ref_loss = reference.loss_and_grads(
        blocks, ids, labels, m=m,
        on_grad=lambda block, g: squares.__setitem__(block, jax.tree.map(lambda x: jnp.sum(x * x), g)))
    ref_sq = _collect(jax.device_get(squares), groups)

    model = Qwen3NextForCausalLM(Qwen3NextConfig.from_hf(m),
                                 BackendConfig(dtype="float32", remat_policy="none"))
    params = adapter.from_reference(weights.stack_layers(blocks, groups))
    want = jax.tree.map(lambda x: (x.shape, x.dtype), model.abstract_params(jnp.float32))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), params) == want

    def loss_fn(p):
        logits, _ = model(p, ids, training=True)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, labels[..., None], -1).mean()

    loss, grads = jax.value_and_grad(loss_fn)(params)
    prog_sq = jax.device_get(optstate.layer_sums(
        adapter.to_reference(jax.tree.map(jnp.square, grads)), groups))
    return (float(loss), prog_sq), (float(ref_loss), ref_sq)


@pytest.mark.parametrize("kinds,extra", [
    ("L", {}), ("F", {}),
    ("L", {"num_experts": 8, "first_held_expert": 0}),  # the uncut layer: all 8 held
    ("LLLFLLLF", {"num_experts": 2}),
], ids=["delta_net", "gated_attention", "moe_whole", "two_periods_2_of_8_held"])
def test_program_matches_the_plain_reference(kinds, extra):
    with jax.default_matmul_precision("highest"):
        m = _model_dict(kinds, **extra)
        if "first_held_expert" in extra:
            m.pop("router_n_experts")
        (loss, prog_sq), (ref_loss, ref_sq) = _both_sides(m, seed=3)
    assert loss == pytest.approx(ref_loss, abs=2e-5)
    assert set(prog_sq) == set(ref_sq)
    for leaf, want in ref_sq.items():
        got = np.sqrt(np.atleast_1d(prog_sq[leaf]))
        want = np.sqrt(np.atleast_1d(want))
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=1e-7, err_msg=leaf)
        assert (want > 0).all(), leaf  # every leaf takes a gradient


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four chips share the tiny layer's 8 experts, 2 each. The routed parts of all four
    shares, with the gated shared expert (which every chip computes alike) counted once,
    sum to what the uncut reference gives for the whole layer: in the reference, and in the
    program's MoE block run as each share."""
    whole = _model_dict("L", num_experts=8, first_held_expert=0)
    whole.pop("router_n_experts")
    d_whole = reference.dims(whole)
    p = _spread(weights.make_blocks(reference, whole, 11, "float32"), 11)["layer_0"]
    t = jax.random.normal(jax.random.key(5), (96, d_whole["D"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference._moe(p, t[None], d_whole)[0]
        shared = reference.shared_expert(p, t)
        routed_sum, program_sum = 0.0, 0.0
        for first in (0, 2, 4, 6):
            m = _model_dict("L", num_experts=2, router_n_experts=8, first_held_expert=first)
            d = reference.dims(m)
            share = dict(p, experts_gate_up=p["experts_gate_up"][first:first + 2],
                         experts_down=p["experts_down"][first:first + 2])
            routed_sum = routed_sum + reference.routed_experts(share, t, d)
            cfg = Qwen3NextConfig.from_hf(m).moe
            assert (cfg.n_routed_experts, cfg.held_experts, cfg.first_held_expert) == (8, 2, first)
            stacked = {f"linear_layers.{k}": v[None] for k, v in share.items()}
            moe_params = jax.tree.map(lambda a: a[0],
                                      adapter.from_reference(stacked)["linear_layers"]["moe"])
            y, _, load = moe_forward(cfg, moe_params, t, dispatcher="ragged")
            assert float(load.sum()) == t.shape[0] * d["K"]  # the router still picks among all 8
            program_sum = program_sum + y
    np.testing.assert_allclose(np.asarray(routed_sum + shared), np.asarray(want), atol=2e-6)
    np.testing.assert_allclose(np.asarray(program_sum - 3 * shared), np.asarray(want), atol=2e-6)
    assert float(jnp.abs(shared).max()) > 1e-4 and float(jnp.abs(routed_sum).max()) > 1e-4


def test_the_token_by_token_rule_matches_the_chunked_rule_and_a_carried_state_shows():
    """At a SLOW decay (1/e over some 600 tokens) and weak writes over ten of the program's
    chunks, so that what a chunk hands the next is most of the result: the reference's
    recurrence, one token at a time, against the program's chunked form with its triangular
    solve; the state carried in still shows at the last token; a token changed early moves
    every later output of its row (through the state, across the reference's checkpointed
    chunks) and no earlier one."""
    rng = np.random.RandomState(0)
    B, S, H, dk, dv = 2, 640, 3, 16, 24
    q, k = (jnp.asarray(rng.randn(B, S, H, dk), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.randn(B, S, H, dv), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.0005, 0.003, (B, S, H)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.01, 0.1, (B, S, H)), jnp.float32)
    state = jnp.asarray(rng.randn(B, H, dk, dv), jnp.float32)
    with jax.default_matmul_precision("highest"):
        theirs, final = reference.delta_rule(l2norm(q), l2norm(k), v, g, beta, state=state)
        ours, ours_final = chunk_gated_delta_rule(q, k, v, g, beta, chunk_size=64,
                                                  initial_state=state, output_final_state=True)
        from_zero, _ = reference.delta_rule(l2norm(q), l2norm(k), v, g, beta)
        nudged, _ = reference.delta_rule(l2norm(q), l2norm(k), v.at[:, 70].add(1.0), g, beta,
                                         state=state)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), atol=5e-5)
    np.testing.assert_allclose(np.asarray(ours_final), np.asarray(final), atol=5e-5)
    # the state carried in still weighs a hundredth or more at the sequence's end
    assert float(jnp.abs(theirs - from_zero)[:, -1].max()) > 0.01 * float(jnp.abs(theirs)[:, -1].max())
    moved = np.abs(np.asarray(nudged - theirs)).max(axis=(0, 2, 3))
    assert (moved[:70] == 0).all() and (moved[70:] > 0).all()


def _blocks_from_hf(hf, m: dict) -> dict:
    """The published module's ``state_dict`` in the reference's layout: transposes and
    slices of the published fused projections, nothing else (``x @ W`` here)."""
    d = reference.dims(m)
    sd = {k: jnp.asarray(v.detach().numpy()) for k, v in hf.state_dict().items()}
    D, Hk, dk, Hv, dv, r = d["D"], d["Hk"], d["dk"], d["Hv"], d["dv"], d["Hv"] // d["Hk"]
    blocks = {"embed": {"embed": sd["model.embed_tokens.weight"]},
              "head": {"final_norm": sd["model.norm.weight"], "lm_head": sd["lm_head.weight"].T}}
    for i, kind in enumerate(d["kinds"]):
        pre = f"model.layers.{i}."
        mlp = pre + "mlp."
        layer = {"attn_norm": sd[pre + "input_layernorm.weight"],
                 "mlp_norm": sd[pre + "post_attention_layernorm.weight"],
                 "router": sd[mlp + "gate.weight"],
                 "experts_gate_up": jnp.stack([jnp.concatenate(
                     [sd[f"{mlp}experts.{e}.gate_proj.weight"].T,
                      sd[f"{mlp}experts.{e}.up_proj.weight"].T], -1) for e in range(d["E"])]),
                 "experts_down": jnp.stack([sd[f"{mlp}experts.{e}.down_proj.weight"].T
                                            for e in range(d["E"])]),
                 "shared_gate": sd[mlp + "shared_expert.gate_proj.weight"].T,
                 "shared_up": sd[mlp + "shared_expert.up_proj.weight"].T,
                 "shared_down": sd[mlp + "shared_expert.down_proj.weight"].T,
                 "shared_expert_gate": sd[mlp + "shared_expert_gate.weight"].T}
        if kind == "linear":
            a = pre + "linear_attn."
            qkvz = sd[a + "in_proj_qkvz.weight"].T.reshape(D, Hk, -1)  # a key head: q k v z
            ba = sd[a + "in_proj_ba.weight"].T.reshape(D, Hk, 2 * r)
            layer |= {"wq": qkvz[..., :dk], "wk": qkvz[..., dk:2 * dk],
                      "wv": qkvz[..., 2 * dk:2 * dk + r * dv].reshape(D, Hv, dv),
                      "wz": qkvz[..., 2 * dk + r * dv:].reshape(D, Hv, dv),
                      "wb": ba[..., :r].reshape(D, Hv), "wa": ba[..., r:].reshape(D, Hv),
                      "conv_w": sd[a + "conv1d.weight"][:, 0, :], "dt_bias": sd[a + "dt_bias"],
                      "a_log": sd[a + "A_log"], "gated_norm": sd[a + "norm.weight"],
                      "wo": sd[a + "out_proj.weight"].T.reshape(Hv, dv, D)}
        else:
            a = pre + "self_attn."
            n, kv, h = d["n"], d["k"], d["h"]
            qg = sd[a + "q_proj.weight"].T.reshape(D, n, 2 * h)  # a head: q, then its gate
            layer |= {"wq": qg[..., :h], "wg": qg[..., h:],
                      "wk": sd[a + "k_proj.weight"].T.reshape(D, kv, h),
                      "wv": sd[a + "v_proj.weight"].T.reshape(D, kv, h),
                      "q_norm": sd[a + "q_norm.weight"], "k_norm": sd[a + "k_norm.weight"],
                      "wo": sd[a + "o_proj.weight"].T.reshape(n, h, D)}
        blocks[f"layer_{i}"] = layer
    return blocks


def test_the_reference_computes_what_the_published_code_computes():
    """``transformers``' ``Qwen3NextForCausalLM`` (the published modeling code, its plain
    torch path) at the tiny sizes with every expert held, seeded weights, every norm and
    decay spread: the reference's logits are its logits."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    m = _model_dict("LLLF", num_experts=8, first_held_expert=0)
    m.pop("router_n_experts")
    keys = {k: v for k, v in m.items() if k not in ("architectures", "first_held_expert")}
    torch.manual_seed(0)
    hf = transformers.Qwen3NextForCausalLM(transformers.Qwen3NextConfig(**keys)).eval()
    with torch.no_grad():
        for name, p in hf.named_parameters():
            if p.ndim == 1:  # norms at zero or one, dt_bias, A_log: spread them
                p.add_(0.3 * torch.randn_like(p))
    blocks = _blocks_from_hf(hf, m)
    assert {b: {k: tuple(v.shape) for k, v in leaves.items()} for b, leaves in blocks.items()} == {
        b: {k: shape for k, (shape, _) in leaves.items()}
        for b, leaves in reference.block_shapes(m).items()}
    ids = np.random.RandomState(0).randint(0, m["vocab_size"], (2, 48))
    with torch.no_grad():
        theirs = hf(torch.tensor(ids)).logits.float().numpy()
    d = reference.dims(m)
    with jax.default_matmul_precision("highest"):
        x = reference.embed_block(blocks["embed"], jnp.asarray(ids))
        for i, kind in enumerate(d["kinds"]):
            x = reference.layer_block(blocks[f"layer_{i}"], x, m=m, kind=kind)
        x = reference._rms(x, blocks["head"]["final_norm"], d["eps"])
        ours = reference._mm("bsd,dv->bsv", x, blocks["head"]["lm_head"])
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-5, rtol=1e-4)


def test_adapter_round_trip_and_the_float32_leaf():
    cell = spec.Cell(CELL, tiny=True)
    flat = weights.stack_layers(weights.make_blocks(cell.reference, cell.model, 5), cell.layer_groups)
    tree = adapter.from_reference(flat)
    assert tree["linear_layers"]["a_log"].dtype == jnp.float32
    assert tree["linear_layers"]["wqkvz"].dtype == jnp.bfloat16
    d = reference.dims(cell.model)
    r = d["Hv"] // d["Hk"]
    assert tree["linear_layers"]["wqkvz"].shape[1:] == (d["D"], d["Hk"], 2 * d["dk"] + 2 * r * d["dv"])
    assert tree["linear_layers"]["wba"].shape[1:] == (d["D"], d["Hk"], 2 * r)
    assert tree["full_layers"]["wq"].shape[1:] == (d["D"], d["n"], 2 * d["h"])
    # value head j sits with key head j // r: its v columns follow that head's q and k
    np.testing.assert_array_equal(
        np.asarray(tree["linear_layers"]["wqkvz"][0, :, 1, 2 * d["dk"]:2 * d["dk"] + d["dv"]], np.float32),
        np.asarray(flat["linear_layers.wv"][0, :, r], np.float32))
    back = adapter.to_reference(tree)
    assert set(back) == set(flat)
    for name in flat:
        np.testing.assert_array_equal(np.asarray(back[name], np.float32),
                                      np.asarray(flat[name], np.float32))


def test_the_cells_counts():
    """At the published widths, ISSUE 38's arithmetic: 1.1735 B parameters held (9.39 GB at
    8 bytes each), 2.34 GFLOP a token of which the DeltaNet mixers are 55%, the two full
    mixers 23% (their scores 9%), the MoE blocks 13% and the head 10%; 0.625 routed experts
    met a token; the recurrence by its own count 3%."""
    cell = spec.Cell(CELL)
    m = cell.model
    assert reference.parameter_count(m) == 1173540992
    assert reference.parameter_count(m) * 8 / 1e9 == pytest.approx(9.39, abs=0.01)
    parts = reference.matrix_params_per_token(m)
    d = reference.dims(m)
    assert d["kinds"] == ["linear"] * 3 + ["full"] + ["linear"] * 3 + ["full"]
    assert cell.layer_groups == {"linear_layers": [0, 1, 2, 4, 5, 6], "full_layers": [3, 7]}
    assert parts["delta_net_projections"] == 6 * (2048 * 12288 + 2048 * 64 + 4096 * 2048)
    assert parts["attention_projections"] == 2 * (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048)
    assert parts["routed_experts"] == 8 * 0.625 * 3 * 2048 * 512
    assert parts["router"] + parts["shared_expert"] == 8 * (512 * 2048 + 3 * 2048 * 512 + 2048)
    assert parts["head"] == 2048 * 18992
    assert sum(parts.values()) == pytest.approx(345.0e6, rel=1e-3)
    total = flops.flops_per_token(reference, m, cell.seq_len)
    assert total == pytest.approx(2.338e9, rel=1e-3)
    rule = 6 * 3 * 7 * 32 * 128 * 128
    scores = 2 * 12 * 16 * 256 * 4097 / 2
    assert reference.score_flops_per_token(m, 4096) == scores + rule
    delta = 6 * (parts["delta_net_projections"] + parts["delta_net_conv"]) + rule
    moe = 6 * (parts["router"] + parts["shared_expert"] + parts["routed_experts"])
    assert delta / total == pytest.approx(0.55, abs=0.01)
    assert (6 * parts["attention_projections"] + scores) / total == pytest.approx(0.23, abs=0.01)
    assert scores / total == pytest.approx(0.09, abs=0.005) and rule / total == pytest.approx(0.03, abs=0.003)
    assert moe / total == pytest.approx(0.13, abs=0.005)
    assert 6 * parts["head"] / total == pytest.approx(0.10, abs=0.005)
    costs = cell.kernel_cost
    assert set(reference.kernel_costs(m, 1, 4096)) == {"flash_attention", "expert_gemms", "gated_delta"}
    assert costs("gated_delta")["flops"] == rule * 4096
    q_k_v_g_beta = 2 * 16 * 128 + 32 * 128 + 2 * 32
    assert costs("gated_delta")["bytes"] == 6 * 4096 * 2 * ((q_k_v_g_beta + 4096) + (2 * q_k_v_g_beta + 4096))
    rows = 4096 * 10 * 32 / 512
    assert costs("expert_gemms")["flops"] == 8 * 3 * 3 * 2 * rows * 2048 * 512
    assert costs("flash_attention")["flops"] == 2 * 6 * 2 * 16 * 4096 * 4097 / 2 * 256
    assert d["E_all"] == 512 and d["E"] == 32 and d["rot"] == 64 and json.dumps(m)
