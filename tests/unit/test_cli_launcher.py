import textwrap

import pytest

from automodel_tpu.cli.app import RECIPES, _resolve, main as cli_main
from automodel_tpu.launcher.slurm import SlurmConfig, render_script
from automodel_tpu.observability.hlo_costs import UnknownDeviceError
from automodel_tpu.utils.flops import flops_per_token, mfu


class TestCli:
    def test_resolve_known(self):
        fn = _resolve("finetune", "llm")
        assert callable(fn)

    def test_resolve_unknown_exits(self):
        with pytest.raises(SystemExit):
            _resolve("bogus", "llm")

    def test_help_exits_zero(self):
        with pytest.raises(SystemExit) as e:
            cli_main(["--help"])
        assert e.value.code == 0

    def test_cli_runs_recipe(self, tmp_path, cpu_devices):
        cfg = tmp_path / "cfg.yaml"
        cfg.write_text(textwrap.dedent(f"""
            seed: 1
            output_dir: {tmp_path}/out
            model:
              config:
                architectures: [LlamaForCausalLM]
                vocab_size: 64
                hidden_size: 32
                intermediate_size: 64
                num_hidden_layers: 2
                num_attention_heads: 4
                num_key_value_heads: 2
                max_position_embeddings: 64
            distributed: {{dp_shard: 8}}
            backend: {{dtype: float32}}
            dataset:
              _target_: automodel_tpu.data.llm.mock.MockSFTDataset
              vocab_size: 64
              seq_len: 16
              num_samples: 64
            micro_batch_size: 8
            seq_len: 16
            step_scheduler: {{grad_acc_steps: 1, max_steps: 2, handle_sigterm: false}}
            optimizer: {{lr: 1.0e-3}}
            checkpoint: {{enabled: false}}
        """))
        cli_main(["finetune", "llm", "-c", str(cfg)])
        assert (tmp_path / "out" / "training.jsonl").exists()


class TestSlurm:
    def test_render_script(self):
        s = render_script(
            SlurmConfig(job_name="j", nodes=4, account="acct", container_image="img"),
            "finetune", "llm", "/x/cfg.yaml",
        )
        assert "#SBATCH --nodes=4" in s
        assert "NUM_PROCESSES=$SLURM_NNODES" in s
        assert "--container-image=img" in s
        assert "finetune llm -c /x/cfg.yaml" in s


class TestFlops:
    def test_dense_flops_sane(self):
        cfg = {
            "hidden_size": 4096, "num_hidden_layers": 32, "vocab_size": 128256,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "intermediate_size": 14336,
        }
        f = flops_per_token(cfg, 4096)
        # llama-3-8B: ~6*8e9 = 4.8e10 + attention; must be within a factor
        assert 4.5e10 < f < 8e10

    def test_moe_flops_counts_active_only(self):
        base = {
            "hidden_size": 2048, "num_hidden_layers": 4, "vocab_size": 1000,
            "num_attention_heads": 16, "num_key_value_heads": 16,
            "intermediate_size": 8192,
        }
        moe = dict(base, num_experts=64, num_experts_per_tok=4, moe_intermediate_size=1024)
        assert flops_per_token(moe, 128) < flops_per_token(base, 128)

    def test_mfu(self):
        assert mfu(1000, 1e12 / 1000, "TPU v5 lite", 1) == pytest.approx(1000 / 197000, rel=1e-3)
        assert mfu(1000, 1e9, "cpu") is None  # a host has no peak
        with pytest.raises(UnknownDeviceError, match="unknown chip"):
            mfu(1000, 1e9, "unknown chip")
