"""Knowledge-distillation recipe (reference KnowledgeDistillationRecipeForNextTokenPrediction,
recipes/llm/kd.py:145).

A teacher model runs forward-only next to the student; the loss blends hard-label CE
with forward-KL to the teacher's temperature-softened distribution:

    loss = (1 - kd_ratio) * CE(student, labels) + kd_ratio * KL(teacher || student)

The teacher rides through the jitted step as a *frozen* pytree argument (the same
``with_frozen`` path PEFT uses) — no gradients, no optimizer state, donated nothing.

YAML adds two sections to the finetune contract:

.. code-block:: yaml

    teacher_model:
      pretrained_model_name_or_path: /path/to/teacher   # or config: {...}
    kd: {temperature: 1.0, kd_ratio: 0.5}
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.config.loader import ConfigNode
from automodel_tpu.config.cli_overrides import parse_args_and_load_config
from automodel_tpu.models.auto import AutoModelForCausalLM, load_hf_config
from automodel_tpu.ops.losses import kd_loss, masked_cross_entropy
from automodel_tpu.recipes.llm.train_ft import TrainFinetuneRecipeForNextTokenPrediction
from automodel_tpu.training.train_step import (
    count_label_tokens,
    jit_train_step,
    make_train_step,
)

logger = logging.getLogger(__name__)

__all__ = ["KnowledgeDistillationRecipe", "main"]


class KnowledgeDistillationRecipe(TrainFinetuneRecipeForNextTokenPrediction):
    def _build_teacher(self):
        cfg = self.cfg
        t_cfg = cfg.get("teacher_model")
        if t_cfg is None:
            raise ValueError("kd recipe needs a teacher_model section")
        pretrained = t_cfg.get("pretrained_model_name_or_path")
        with self.mesh:
            if pretrained:
                self.teacher, self.teacher_params = AutoModelForCausalLM.from_pretrained(
                    pretrained, backend=self.backend, dtype=jnp.float32, rules=self.rules
                )
            else:
                model_cfg = t_cfg.get("config")
                if model_cfg is None:
                    raise ValueError("teacher_model needs pretrained_model_name_or_path or config")
                hf = model_cfg.to_dict() if isinstance(model_cfg, ConfigNode) else dict(model_cfg)
                self.teacher = AutoModelForCausalLM.from_config(hf, backend=self.backend)
                shardings = self.rules.tree_sharding(self.teacher.logical_axes())
                init_fn = jax.jit(lambda k: self.teacher.init(k, jnp.float32), out_shardings=shardings)
                self.teacher_params = init_fn(self.rng.key("teacher_init"))
        n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(self.teacher_params))
        logger.info("teacher: %s (%.1fM params)", type(self.teacher).__name__, n / 1e6)

    def _build_train_step(self):
        self._build_teacher()
        temperature = float(self.cfg.get("kd.temperature", 1.0))
        kd_ratio = float(self.cfg.get("kd.kd_ratio", 0.5))
        divergence = str(self.cfg.get("kd.divergence", "forward_kl"))
        self._static_log_fields = {"kd_ratio": kd_ratio, "temperature": temperature,
                                   "kd_divergence": divergence}
        logger.info("kd: ratio=%s T=%s divergence=%s", kd_ratio, temperature, divergence)
        if self.mesh_ctx.pp > 1:
            return self._build_pp_train_step(temperature, kd_ratio, divergence)

        teacher_is_moe = (getattr(self.teacher.config, "moe", None) is not None
                          or getattr(getattr(self.teacher.config, "text", None),
                                     "moe", None) is not None)

        def kd_core(student_params, teacher_params, batch, num_label_tokens):
            s_kw = ({"token_mask": batch["segment_ids"] != 0, "training": True}
                    if self._moe_config is not None else {})
            out = self.model(
                student_params, batch["input_ids"], positions=batch["positions"],
                segment_ids=batch["segment_ids"], rules=self.rules, **s_kw,
            )
            # MoE students return (logits, stats) — same contract train_ft's
            # _forward_loss consumes; expert_load flows to metrics/gate-bias
            student_logits, stats = out if isinstance(out, tuple) else (out, None)
            t_kw = ({"token_mask": batch["segment_ids"] != 0, "training": False}
                    if teacher_is_moe else {})
            t_out = self.teacher(
                teacher_params, batch["input_ids"], positions=batch["positions"],
                segment_ids=batch["segment_ids"], rules=self.rules, **t_kw,
            )
            teacher_logits = jax.lax.stop_gradient(
                t_out[0] if isinstance(t_out, tuple) else t_out
            )
            ce = masked_cross_entropy(student_logits, batch["labels"], num_label_tokens)
            kd = kd_loss(
                student_logits, teacher_logits, batch["labels"],
                temperature=temperature, num_label_tokens=num_label_tokens,
                divergence=divergence,
            )
            loss = (1.0 - kd_ratio) * ce + kd_ratio * kd
            if stats is None:
                return loss
            aux = {"expert_load": stats["expert_load"]}
            if "dropped_token_frac" in stats:
                aux["dropped_token_frac"] = stats["dropped_token_frac"]
            if stats["aux_loss"] is not None:
                mb_tokens = count_label_tokens(batch["labels"]).astype(jnp.float32)
                loss = loss + self._moe_config.aux_loss_coeff * stats["aux_loss"] * (
                    mb_tokens / num_label_tokens
                )
            return loss, aux

        use_dropout = self.peft is not None and self.peft.dropout > 0.0
        if self.peft is not None:
            # kd + peft (reference composes them, infrastructure.py:303): the
            # frozen slot carries BOTH the teacher and the student's lora base
            from automodel_tpu.peft.lora import lora_merged_loss

            kd_forward = lora_merged_loss(
                lambda merged, fr, b, n: kd_core(merged, fr["teacher"], b, n),
                lambda fr: fr["base"], self.peft, use_dropout,
            )
        else:
            def kd_forward(params, frozen, batch, num_label_tokens):
                return kd_core(params, frozen["teacher"], batch, num_label_tokens)

        self._step_needs_rng = use_dropout
        post_update = (self._post_update()
                       if (self._moe_config is not None and self.peft is None) else None)
        step = make_train_step(kd_forward, self.optimizer, with_frozen=True,
                               guard_nonfinite=self._check_nan_grads,
                               pass_rng=use_dropout, post_update=post_update)
        return jit_train_step(step, self.train_params, self.opt_state)

    def _build_pp_train_step(self, temperature: float, kd_ratio: float,
                             divergence: str = "forward_kl"):
        """kd x pp (reference composes them through its one sequencing path,
        infrastructure.py:303): the STUDENT's layer stack pipelines over pp and
        yields final hidden states outside the manual region; the student head,
        the teacher forward, and the blended CE+KL loss then run per microbatch
        in plain GSPMD (lax.map — one microbatch's logits pair live at a time).
        The teacher is not pipelined: its layer stacks stay sharded by the rules
        (the pp axis acts as an extra FSDP axis for it), gathered per layer
        during its forward-only pass."""
        from automodel_tpu.models.common.transformer import embed_lookup
        from automodel_tpu.parallel.pipeline import (
            make_dense_decoder_pp_hidden, make_head_logits, make_moe_pp_hidden,
        )
        from automodel_tpu.training.train_step import make_pp_train_step

        cfg, backend = self.model.config, self.model.backend
        dtype = backend.jnp_dtype
        virtual = int(self.cfg.get("distributed.pp_virtual_stages", 1))
        head_logits = make_head_logits(cfg, dtype)
        is_moe = self._moe_config is not None
        teacher_is_moe = (getattr(self.teacher.config, "moe", None) is not None
                          or getattr(getattr(self.teacher.config, "text", None),
                                     "moe", None) is not None)
        if is_moe:
            # MoE students ride the same pipelined hidden-state path train_ft's
            # MoE pp loss is built on (make_moe_pp_loss); expert_load flows to
            # the gate-bias post-update exactly as in the non-KD recipe
            layers_key = "moe_layers"
            student_hidden = make_moe_pp_hidden(
                self.model, self.mesh, self.rules, seq_len_hint=self.seq_len,
                circular_repeats=virtual,
            )
        else:
            layers_key = "layers"
            dense_hidden = make_dense_decoder_pp_hidden(
                cfg, backend, self.mesh, circular_repeats=virtual
            )

            def student_hidden(params, batch_stack, n):
                other = {k: v for k, v in params.items() if k != "layers"}
                x_stack = {
                    "h": embed_lookup(other["embed"], batch_stack["input_ids"],
                                      dtype, self.rules,
                                      scale=getattr(cfg, "embedding_multiplier", 1.0)),
                    "positions": batch_stack["positions"],
                    "segment_ids": batch_stack["segment_ids"],
                }
                return dense_hidden(params["layers"], x_stack), 0.0, {}

        def kd_pp_core(student_params, teacher_params, batch_stack, n):
            h_stack, aux_loss, extras = student_hidden(student_params, batch_stack, n)
            other = {k: v for k, v in student_params.items() if k != layers_key}

            def mb_loss(args):
                h_mb, mb = args
                s_logits = head_logits(other, h_mb)
                t_kw = ({"token_mask": mb["segment_ids"] != 0, "training": False}
                        if teacher_is_moe else {})
                t_out = self.teacher(
                    teacher_params, mb["input_ids"], positions=mb["positions"],
                    segment_ids=mb["segment_ids"], rules=self.rules, **t_kw,
                )
                t_logits = jax.lax.stop_gradient(
                    t_out[0] if isinstance(t_out, tuple) else t_out
                )
                ce = masked_cross_entropy(s_logits, mb["labels"], n)
                kd = kd_loss(s_logits, t_logits, mb["labels"],
                             temperature=temperature, num_label_tokens=n,
                             divergence=divergence)
                return (1.0 - kd_ratio) * ce + kd_ratio * kd

            loss = jax.lax.map(mb_loss, (h_stack, batch_stack)).sum() + aux_loss
            return (loss, extras) if is_moe else loss

        use_dropout = self.peft is not None and self.peft.dropout > 0.0
        if self.peft is not None:
            from automodel_tpu.peft.lora import lora_merged_loss

            kd_forward = lora_merged_loss(
                lambda merged, fr, bs, n: kd_pp_core(merged, fr["teacher"], bs, n),
                lambda fr: fr["base"], self.peft, use_dropout,
            )
        else:
            def kd_forward(params, frozen, batch_stack, n):
                return kd_pp_core(params, frozen["teacher"], batch_stack, n)

        self._step_needs_rng = use_dropout
        post_update = self._post_update() if (is_moe and self.peft is None) else None
        step = make_pp_train_step(kd_forward, self.optimizer, with_frozen=True,
                                  guard_nonfinite=self._check_nan_grads,
                                  post_update=post_update, pass_rng=use_dropout)
        return jit_train_step(step, self.train_params, self.opt_state)

    @property
    def _kd_frozen_arg(self):
        frozen = {"teacher": self.teacher_params}
        if self.peft is not None:
            frozen["base"] = self.params
        return frozen

    def run_train_validation_loop(self):
        # thread the teacher (and, under peft, the student base) through the
        # frozen slot; the base loop's peft extra is replaced by _kd_frozen_arg
        # but its trailing dropout rng (when _step_needs_rng) passes through
        jitted = self._train_step
        self._train_step = lambda p, o, stack, *extra: jitted(
            p, o, stack, self._kd_frozen_arg,
            *((extra[-1],) if self._step_needs_rng else ()),
        )
        super().run_train_validation_loop()


def main(cfg: ConfigNode | None = None, argv=None):
    if cfg is None:
        cfg = parse_args_and_load_config(argv)
    recipe = KnowledgeDistillationRecipe(cfg)
    recipe.setup()
    recipe.run_train_validation_loop()
    return recipe


if __name__ == "__main__":
    main()
