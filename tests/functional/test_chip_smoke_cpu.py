"""Rehearsal 1 of ``chip_smoke.py``, kept as a test: its one-chip route end to end
on the CPU at a toy size, in this process, with the device assertion stubbed and
the kernels interpreted. What only the chip can show (compiled kernels in the HLO)
must be refused here, with the reason — the smoke has no CPU verdict of its own."""

import json
import signal
import types

import pytest

TOY = [
    "--model.config.vocab_size", "512", "--model.config.hidden_size", "64",
    "--model.config.intermediate_size", "128", "--model.config.num_hidden_layers", "2",
    "--model.config.num_attention_heads", "4", "--model.config.num_key_value_heads", "2",
    "--model.config.head_dim", "16", "--dataset.vocab_size", "512",
    "--dataset.seq_len", "64", "--seq_len", "64",
    "--backend.attention", "flash_interpret",
    # the test process has 8 virtual devices and the recipe meshes them all:
    # dp 4 x tp 2 also sends the interpreted kernel through the mesh shard_map
    "--distributed.dp_shard", "4", "--distributed.tp", "2",
]


@pytest.fixture
def within_120_s():
    """Per-test time limit (no timeout plugin is installed): SIGALRM in the
    worker's main thread."""
    def too_long(signum, frame):
        raise TimeoutError("the toy chip_smoke rehearsal ran over 120 s")

    old = signal.signal(signal.SIGALRM, too_long)
    signal.alarm(120)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


def test_one_chip_route_at_toy_size(monkeypatch, capsys, tmp_path, within_120_s):
    import chip_smoke
    from automodel_tpu.ops.kernels import KernelResolutionError

    stub = types.SimpleNamespace(platform="tpu", device_kind="stubbed chip")
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda count: [stub] * count)
    monkeypatch.setattr(chip_smoke, "OUT_ROOT", str(tmp_path))
    train_phase, check_on_chip = chip_smoke.train_phase, chip_smoke.check_on_chip
    monkeypatch.setattr(
        chip_smoke, "train_phase",
        lambda name, overrides, clock, **kw: train_phase(
            name, TOY + overrides, clock, loss_margin=0.05, **kw))

    def refused_off_the_chip(name, recipe, result, **expected):
        assert result["kernels"]["attention"] == "flash"  # the kernel's own logic ran
        with pytest.raises(KernelResolutionError, match="interpret mode"):
            check_on_chip(name, recipe, result, **expected)

    monkeypatch.setattr(chip_smoke, "check_on_chip", refused_off_the_chip)
    assert chip_smoke.main([]) == 0

    docs = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    steps = [d for d in docs if "step" in d]
    assert [d["step"] for d in steps] == list(range(1, 9))
    assert all(isinstance(d["loss"], float) for d in steps)
    (summary,) = [d for d in docs if "compile_counts" in d]
    assert summary["compile_counts"]["aot"] == 1 and summary["compile_s"] > 0
    assert summary["compile_cache"]["dir"]
    assert docs[-1] == {"ok": True, "device": {
        "platform": "tpu", "kind": "stubbed chip", "count": 1}}
