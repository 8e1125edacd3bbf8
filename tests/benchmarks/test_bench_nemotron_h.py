"""The Nemotron-H family's benchmark files against the program, on the CPU at small sizes
and seeded random weights: ``reference/nemotron_h.py`` (token-by-token recurrence, a loop
over the held experts) against ``models/nemotron_v3`` (chunked scan, sorted grouped
GEMMs) for each layer kind alone and for the cell's 11-layer pattern, loss and per-leaf
gradient norms; the adapter's tree against the program's; the counts the cell's
``flops_per_token`` and roofline readers are built from.

The cell is in ``BENCHMARK.json`` and is cut to an eighth of the vocabulary, so
``vocab_size`` stands in its ``reduced``, where the guide lists a sliced vocabulary.
``test_bench_units.py`` refuses every ``reduced`` key that ends in ``_size`` and fails on
that one key until a ``benchmark`` PR names the widths it means (PERF.md section 7); the
first test here holds the entries to everything else that test asks. The cell and its
control are rehearsed with the accepted cells in ``test_bench_rehearse.py`` and
``test_bench_control.py``; here the traced rehearsal, and two planted faults."""

import importlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.nemotron_v3.model import NemotronHForCausalLM, NemotronV3Config
from benchmarks.adapters import nemotron_h as adapter
from benchmarks.harness import flops, optstate, spec, weights
from benchmarks.reference import nemotron_h as reference
from benchmarks.reference.train import _collect

from tests.benchmarks.rehearsal import rehearse

CELL = "nemotron3super_pretrain_4k"
CONFIG = "nemotron-3-super-120b-a12b-p11-ep32"


NEW_METRICS = ("mamba_device_ms", "ssd_scan_roofline", "moe_latent_proj_device_ms",
               "moe_held_rows_share")
JOINED_METRICS = ("moe_device_ms", "expert_gemm_roofline")


def test_the_cells_entries_keep_the_contract_but_for_the_sliced_vocabulary():
    """What ``test_bench_units.py`` asks of every entry, asked of these: one key stands in
    the way, ``vocab_size`` in ``reduced``, which the guide lists there when the vocabulary
    is sliced. Every width is the catalog row's."""
    from tests.unit.test_nemotron3_config import PUBLISHED

    bench = spec.benchmark_json()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    config = bench["configs"][-1]
    cell = bench["workloads"][-1]
    per_layer = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    assert (config["name"], cell["name"], cell["config"]) == (CONFIG, CELL, CONFIG)
    assert cell["chips"] == 1 and cell["traffic"] == "pretrain_4k"
    assert [m["name"] for m in bench["per_layer"][-len(NEW_METRICS):]] == list(NEW_METRICS)
    for entry in (config, cell, *per_layer):
        assert name.match(entry["name"])
        assert all(1 <= len(entry[k]) <= 200 for k in ("why", "layer", "source") if k in entry)
    refused = [k for k in config["reduced"] if k.endswith(("_dim", "_rank", "_size"))]
    assert refused == ["vocab_size"]
    with open(os.path.join(spec.ROOT, config["file"])) as f:
        file = json.load(f)
    assert file["reduced"] == config["reduced"] == list(file["published"])
    differs = {k for k, v in PUBLISHED.items() if file.get(k) != v}
    assert differs == set(config["reduced"])
    assert all(file["published"][k] == PUBLISHED[k] for k in differs)
    moved = {m["name"] for m in bench["end_to_end"]}
    for metric in per_layer:
        assert metric["workloads"] == [CELL] and metric["moves"] in moved
        assert callable(importlib.import_module("benchmarks.metrics." + metric["name"]).read)
    for metric in bench["per_layer"]:
        if metric["name"] in JOINED_METRICS:
            assert metric["workloads"] == ["qwen3moe_pretrain_4k", CELL]
    reported = {m["name"] for m in spec.Cell(CELL).per_layer}
    assert reported >= set(NEW_METRICS) | set(JOINED_METRICS)
    assert "mlp_device_ms" not in reported and "linear_ce_roofline" not in reported


def test_a_rehearsed_run_of_the_cell_is_correct_and_counts_the_held_rows(capsys, tmp_path):
    result, lines, failed = rehearse(capsys, "--workload", CELL, "--seed", str(2**31 + 7),
                                     "--trace", "1", "--out", str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0, failed
    # the program's counter is read on a CPU too; no device reader finds a trace there
    assert set(result["metrics"]) == {"data_wait_ms", "compiles_in_window", "step_hbm_gib",
                                      "moe_held_rows_share"}
    assert 0 < result["metrics"]["moe_held_rows_share"]["value"] < 100
    compared = " ".join(line for line in lines if "worst:" in line)
    assert all(group in compared for group in ("mamba_layers.", "moe_layers."))


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


def _model_dict(pattern: str, **kw) -> dict:
    m = dict(spec.Cell(CELL, tiny=True).model, hybrid_override_pattern=pattern,
             num_hidden_layers=len(pattern))
    m.update(kw)
    return m


def _both_sides(m: dict, seed: int, rows: int = 2, seq: int = 64):
    """Loss and per-leaf sums of squared gradients: (program, reference), float32."""
    groups = reference.layer_groups(m)
    blocks = weights.make_blocks(reference, m, seed, "float32")
    # spread the constant leaves too, or a wrong bias or skip term would not show
    key = jax.random.key(seed + 1)
    for i, (name, leaves) in enumerate(blocks.items()):
        for j, leaf in enumerate(sorted(leaves)):
            if leaf != "router_bias" and leaves[leaf].ndim == 1:
                noise = jax.random.normal(jax.random.fold_in(key, 100 * i + j), leaves[leaf].shape)
                leaves[leaf] = leaves[leaf] + 0.3 * noise
    rng = np.random.RandomState(seed)
    ids = jnp.asarray(rng.randint(0, m["vocab_size"], (rows, seq)))
    labels = jnp.asarray(rng.randint(0, m["vocab_size"], (rows, seq)))

    squares = {}
    ref_loss = reference.loss_and_grads(
        blocks, ids, labels, m=m,
        on_grad=lambda block, g: squares.__setitem__(block, jax.tree.map(lambda x: jnp.sum(x * x), g)))
    ref_sq = _collect(jax.device_get(squares), groups)

    model = NemotronHForCausalLM(NemotronV3Config.from_hf(m),
                                 BackendConfig(dtype="float32", remat_policy="none"))
    params = adapter.from_reference(weights.stack_layers(blocks, groups))
    want = jax.tree.map(lambda x: (x.shape, x.dtype), model.abstract_params(jnp.float32))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), params) == want

    def loss_fn(p):
        logits, _ = model(p, ids, segment_ids=jnp.ones_like(ids), training=True)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, labels[..., None], -1).mean()

    loss, grads = jax.value_and_grad(loss_fn)(params)
    prog_sq = jax.device_get(optstate.layer_sums(
        adapter.to_reference(jax.tree.map(jnp.square, grads)), groups))
    return (float(loss), prog_sq), (float(ref_loss), ref_sq)


@pytest.mark.parametrize("pattern,extra", [
    ("M", {}), ("*", {}), ("E", {}),
    ("E", {"n_routed_experts": 8, "first_held_expert": 0}),  # the uncut layer: all 8 held
    ("MEMEMEMEM*E", {}),
], ids=["mamba", "attention", "latent_moe_share", "latent_moe_whole", "period_of_11"])
def test_program_matches_the_plain_reference(pattern, extra):
    with jax.default_matmul_precision("highest"):
        m = _model_dict(pattern, **extra)
        if extra:
            m.pop("router_n_experts")
        (loss, prog_sq), (ref_loss, ref_sq) = _both_sides(m, seed=3)
    assert loss == pytest.approx(ref_loss, abs=2e-5)
    assert set(prog_sq) == set(ref_sq)
    for leaf, want in ref_sq.items():
        got = np.sqrt(np.atleast_1d(prog_sq[leaf]))
        want = np.sqrt(np.atleast_1d(want))
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=1e-7, err_msg=leaf)
    # the router's selection buffer takes no gradient; every other leaf does
    for leaf, want in ref_sq.items():
        assert (np.atleast_1d(want) > 0).all() != leaf.endswith("router_bias"), leaf


def test_a_token_by_token_recurrence_and_a_changed_state_show():
    """The reference's recurrence really is one: a token changed early moves every later
    output of that row (through the state, across the reference's checkpointed chunks),
    and no earlier one."""
    d = reference.dims(_model_dict("M"))
    rng = np.random.RandomState(0)
    B, S = 1, 4 * d["chunk"]
    x = jnp.asarray(rng.randn(B, S, d["H"], d["P"]), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (B, S, d["H"]))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, d["H"]), jnp.float32)
    Bm = jnp.asarray(rng.randn(B, S, d["G"], d["N"]), jnp.float32)
    Cm = jnp.asarray(rng.randn(B, S, d["G"], d["N"]), jnp.float32)
    y = reference._recurrence(x, dt, A, Bm, Cm, d["chunk"])
    y2 = reference._recurrence(x.at[:, 5].add(1.0), dt, A, Bm, Cm, d["chunk"])
    moved = np.abs(np.asarray(y2 - y)).max(axis=(0, 2, 3))
    assert (moved[:5] == 0).all() and (moved[5:] > 0).all()


def test_adapter_round_trip_and_the_two_float32_leaves():
    cell = spec.Cell(CELL, tiny=True)
    flat = weights.stack_layers(weights.make_blocks(cell.reference, cell.model, 5), cell.layer_groups)
    tree = adapter.from_reference(flat)
    assert tree["mamba_layers"]["a_log"].dtype == jnp.float32
    assert tree["moe_layers"]["moe"]["gate"]["score_correction_bias"].dtype == jnp.float32
    assert tree["mamba_layers"]["in_proj"].dtype == jnp.bfloat16
    back = adapter.to_reference(tree)
    assert set(back) == set(flat)
    for name in flat:
        np.testing.assert_array_equal(np.asarray(back[name], np.float32),
                                      np.asarray(flat[name], np.float32))


def test_the_cells_counts():
    """At the published widths, ISSUE 29's arithmetic: 1.431 B parameters held, 5.8 GFLOP a
    token of which the Mamba-2 layers are 58%, the LatentMoE layers 30% and the head 7%;
    0.6875 routed experts met a token."""
    cell = spec.Cell(CELL)
    m = cell.model
    assert reference.parameter_count(m) == 1431132544
    assert reference.parameter_count(m) * 8 / 1e9 == pytest.approx(11.45, abs=0.01)
    parts = reference.matrix_params_per_token(m)
    d = reference.dims(m)
    assert parts["routed_experts"] == 5 * 0.6875 * 2 * 1024 * 2688
    assert parts["head"] == 4096 * 16384
    total = flops.flops_per_token(reference, m, cell.seq_len)
    assert 5.6e9 < total < 6.0e9
    mamba = 6 * (parts["mamba_projections"] + parts["mamba_conv"]) + 5 * 3 * 4 * 128 * 64 * 128
    moe = 6 * (parts["router"] + parts["latent_projections"] + parts["shared_expert"]
               + parts["routed_experts"])
    assert mamba / total == pytest.approx(0.58, abs=0.01)
    assert moe / total == pytest.approx(0.30, abs=0.015)
    assert 6 * parts["head"] / total == pytest.approx(0.07, abs=0.005)
    costs = cell.kernel_cost
    assert set(reference.kernel_costs(m, 1, 4096)) == {"flash_attention", "expert_gemms", "ssd_scan"}
    assert costs("ssd_scan")["flops"] == 5 * 3 * 4 * 128 * 64 * 128 * 4096
    x_dt_b_c = 8192 + 128 + 2 * 8 * 128
    assert costs("ssd_scan")["bytes"] == 5 * 4096 * 2 * ((x_dt_b_c + 8192) + (2 * x_dt_b_c + 8192))
    rows = 4096 * 22 * 16 / 512
    assert costs("expert_gemms")["flops"] == 5 * 3 * 2 * 2 * rows * 1024 * 2688
    assert d["E_all"] == 512 and d["E"] == 16 and json.dumps(m)  # the cut, and plain data
