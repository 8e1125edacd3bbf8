"""What ``correct`` has to catch, at the tiny sizes a test run can hold: the cell's
control (the program's own fp8 projections, the precision below the bfloat16 the
configuration states) and a timed path broken underneath (a step that hands its
parameters back unchanged, a stream that leaves out half of each row). On the chip the
control was read at the cells' own sizes (PERF.md, PR 24)."""

import pytest

from benchmarks.generators import token_stream
from benchmarks.harness import spec
from tests.benchmarks.rehearsal import rehearse


@pytest.mark.parametrize("workload", [w["name"] for w in spec.benchmark_json()["workloads"]])
def test_the_control_comes_out_as_not_correct(workload, capsys, tmp_path):
    result, _, failed = rehearse(capsys, "--workload", workload, "--seed", "21", "--control",
                              "--out", str(tmp_path))
    assert result["correct"] is False
    assert "first_gradient_norm_gap" in failed


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
    result, _, failed = rehearse(capsys, "--workload", "mistral7b_pretrain_4k", "--seed", "22",
                              "--out", str(tmp_path))
    assert result["correct"] is False
    assert "parameter_change_norm_gap_after_2" in failed


def test_a_part_of_the_batch_left_out_is_not_correct(capsys, tmp_path, monkeypatch):
    real = token_stream.Dataset.__iter__

    def half_rows(self):
        for example in real(self):
            example["prompt_len"] = self.seq_len // 2  # the first half carries no loss
            yield example

    monkeypatch.setattr(token_stream.Dataset, "__iter__", half_rows)
    result, _, failed = rehearse(capsys, "--workload", "mistral7b_pretrain_4k", "--seed", "23",
                              "--out", str(tmp_path))
    assert result["correct"] is False
    assert failed & {"loss_step_1_gap", "first_gradient_norm_gap"}
