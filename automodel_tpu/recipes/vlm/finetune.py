"""VLM finetune recipe (reference FinetuneRecipeForVLM, recipes/vlm/finetune.py:469).

Subclasses the LLM finetune recipe: image-text model factory, VLM collation with
image-token expansion, and a ``freeze`` section (reference freeze_config) that
splits params into trainable/frozen *subtrees* — frozen parts ride through the
jitted step as a non-differentiated argument (the same mechanism PEFT uses), so
optimizer state only covers what trains.

.. code-block:: yaml

    model:
      pretrained_model_name_or_path: /path/to/llava   # or config: {...}
    freeze:
      freeze_vision_tower: true      # reference default
      freeze_language_model: false
      freeze_projector: false
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.config.loader import ConfigNode
from automodel_tpu.config.cli_overrides import parse_args_and_load_config
from automodel_tpu.data.vlm.collate import vlm_collate
from automodel_tpu.models.auto import AutoModelForImageTextToText, load_hf_config
from automodel_tpu.ops.losses import masked_cross_entropy
from automodel_tpu.recipes.llm.train_ft import TrainFinetuneRecipeForNextTokenPrediction
from automodel_tpu.training.train_step import jit_train_step, make_train_step

logger = logging.getLogger(__name__)

__all__ = ["FinetuneRecipeForVLM", "main"]

# freeze-config key -> candidate param-subtree names across families (llava
# splits vision_tower/language_model/projector; qwen-vl nests the merger inside
# a flat "visual" tower beside flat language keys; omni adds "audio")
_FREEZE_KEYS = {
    "freeze_vision_tower": ("vision_tower", "visual"),
    "freeze_audio_tower": ("audio",),
    "freeze_language_model": (
        "language_model", "embed", "final_norm", "layers", "dense_layers",
        "moe_layers", "lm_head",
    ),
    "freeze_projector": ("projector",),
}


class FinetuneRecipeForVLM(TrainFinetuneRecipeForNextTokenPrediction):
    # -- model --------------------------------------------------------------
    def _build_model_and_params(self):
        cfg = self.cfg
        pretrained = cfg.get("model.pretrained_model_name_or_path")
        with self.mesh:
            if pretrained:
                self.hf_config = load_hf_config(pretrained)
                # fence BEFORE the rules-applied load: an unsupported family
                # would otherwise die on vision-block sharding divisibility
                # with an opaque pjit error instead of the clean fence
                self.model = AutoModelForImageTextToText.from_config(
                    self.hf_config, backend=self.backend
                )
                self._check_pp_support()
                self.model, self.params = AutoModelForImageTextToText.from_pretrained(
                    pretrained, backend=self.backend, dtype=jnp.float32, rules=self.rules
                )
            else:
                model_cfg = cfg.get("model.config")
                if model_cfg is None:
                    raise ValueError("config needs model.pretrained_model_name_or_path or model.config")
                self.hf_config = model_cfg.to_dict() if isinstance(model_cfg, ConfigNode) else dict(model_cfg)
                self.model = AutoModelForImageTextToText.from_config(self.hf_config, backend=self.backend)
                self._check_pp_support()
                shardings = self.rules.tree_sharding(self.model.logical_axes())
                init_fn = jax.jit(lambda k: self.model.init(k, jnp.float32), out_shardings=shardings)
                self.params = init_fn(self.rng.key("model_init"))
        n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(self.params))
        logger.info("model: %s (%.1fM params)", type(self.model).__name__, n_params / 1e6)

    def _check_pp_support(self):
        """Fence BEFORE param init: under pp the sharding rules put the layer
        axis on pp, which only makes sense for families whose text stack we
        pipeline (vision-tower blocks of an unsupported family would otherwise
        fail sharding-divisibility first with an opaque pjit error)."""
        if self.mesh_ctx.pp <= 1:
            return
        if hasattr(self.model, "merged_embeds"):
            return  # LLaVA lineage: dense text stack behind merged embeds
        if getattr(self.model, "pp_hidden_supported", False):
            return  # mrope/deepstack families with a model-provided pp hidden path
        raise NotImplementedError(
            "vlm + pp is wired for models exposing merged_embeds (LLaVA lineage) "
            "or a make_pp_hidden pipelined path (qwen3-vl deepstack); this "
            "family interleaves vision state into the layer stream without one"
        )

    def _build_peft(self):
        # freeze split (reference freeze_config, vlm/finetune.py:86-113)
        freeze_cfg = self.cfg.get("freeze") or ConfigNode({"freeze_vision_tower": True})
        frozen_keys = [
            key
            for cfg_key, tree_keys in _FREEZE_KEYS.items()
            if freeze_cfg.get(cfg_key, cfg_key == "freeze_vision_tower")
            for key in tree_keys
        ]
        self.frozen_keys = [k for k in frozen_keys if k in self.params]
        if len(self.frozen_keys) == len(self.params):
            raise ValueError("freeze config freezes every submodule; nothing to train")
        self.frozen_params = {k: self.params[k] for k in self.frozen_keys}
        self.train_params = {k: v for k, v in self.params.items() if k not in self.frozen_keys}
        logger.info("vlm freeze: frozen=%s trainable=%s", self.frozen_keys, list(self.train_params))

        # vlm + peft (reference composes them freely, infrastructure.py:303):
        # LoRA factors attach to the UNFROZEN subtrees; the base becomes part of
        # the frozen argument and only the adapter trains
        self.peft = None
        peft_cfg = self.cfg.get("peft")
        if peft_cfg is not None:
            from automodel_tpu.peft.lora import (
                PeftConfig, count_lora_params, init_lora_params, lora_logical_axes,
            )

            self.peft = PeftConfig.from_dict(peft_cfg.to_dict())
            axes = {k: v for k, v in self.model.logical_axes().items()
                    if k in self.train_params}
            host_lora = init_lora_params(
                self.train_params, axes, self.peft, self.rng.key("lora_init")
            )
            shardings = self.rules.tree_sharding(lora_logical_axes(axes, self.peft))
            self.lora_base = self.train_params  # frozen base of the trainable subtrees
            self.train_params = jax.tree.map(
                lambda x, s: jax.device_put(x, s), host_lora, shardings
            )
            logger.info(
                "vlm peft: %d adapter params on %s",
                count_lora_params(self.train_params), list(axes),
            )

    # -- data ---------------------------------------------------------------
    def _wrap_dataset_and_collate(self, dataset, pad_id: int):
        from automodel_tpu.data.vlm.collate_fns import (
            kimi_vl_collate, qwen3_omni_collate, qwen_vl_collate,
        )

        mcfg = self.model.config
        name = type(self.model).__name__
        # vlm.image_size: (grid_h, grid_w) in PATCHES — one fixed grid per config
        # keeps every media shape static under jit
        image_size = self.cfg.get("vlm.image_size")
        if image_size is not None:
            image_size = tuple(image_size)
        if name == "Qwen3OmniMoeThinkerForConditionalGeneration":
            fn = lambda exs: qwen3_omni_collate(
                exs, self.tokenizer, self.model, self.seq_len, pad_id,
                image_size=image_size,
            )
        elif name == "Qwen3VLMoeForConditionalGeneration":
            fn = lambda exs: qwen_vl_collate(
                exs, self.tokenizer, self.model, self.seq_len, pad_id,
                image_size=image_size,
            )
        elif name in ("KimiVLForConditionalGeneration", "KimiK25VLForConditionalGeneration"):
            fn = lambda exs: kimi_vl_collate(
                exs, self.tokenizer, self.model, self.seq_len, pad_id,
                image_size=image_size,
            )
        else:  # LLaVA composition (single-image, fixed token count)
            fn = lambda exs: vlm_collate(
                exs,
                tokenizer=self.tokenizer,
                seq_len=self.seq_len,
                image_token_id=mcfg.image_token_index,
                num_image_tokens=mcfg.num_image_tokens,
                image_size=mcfg.vision.image_size,
                pad_token_id=pad_id,
            )
        return dataset, fn

    # -- step ---------------------------------------------------------------
    _RESERVED = ("input_ids", "labels", "positions", "segment_ids")

    def _model_kwargs(self, batch):
        """Reassemble the collator's extra batch keys into model-call kwargs
        (coord pairs ride as separate _b/_s arrays so the batch stays a flat
        array pytree)."""
        kw = {}
        for k, v in batch.items():
            if k in self._RESERVED or k.endswith(("_coords_b", "_coords_s")):
                continue
            kw[k] = v
        for prefix in ("visual", "media", "audio"):
            b, s = batch.get(f"{prefix}_coords_b"), batch.get(f"{prefix}_coords_s")
            if b is not None:
                kw[f"{prefix}_coords"] = (b, s)
        return kw

    def _model_forward(self, params, batch, training):
        """Model call with the collator's extra modalities; the shared base
        ``_forward_loss`` keeps the loss + MoE aux/expert-load handling."""
        import inspect

        if not hasattr(self, "_model_call_params"):
            self._model_call_params = set(
                inspect.signature(type(self.model).__call__).parameters
            )
        kw = self._model_kwargs(batch)
        kw["segment_ids"] = batch["segment_ids"]
        kw["rules"] = self.rules if self.mesh.size > 1 else None
        kw["training"] = training
        kw["token_mask"] = batch["segment_ids"] != 0
        if "positions3" not in kw:
            kw["positions"] = batch.get("positions")
        kw = {k: v for k, v in kw.items() if k in self._model_call_params}
        return self.model(params, batch["input_ids"], **kw)

    def _build_stack_shardings(self):
        shardings = super()._build_stack_shardings()
        shardings["replicated"] = self.rules.sharding((None,))
        return shardings

    def _device_put_stack(self, stack):
        """Per-key shardings: (n_micro, B, S) token streams shard over batch;
        flat media tensors (patches, coords, grids) replicate. Shardings are
        built once in setup() — rebuilding NamedShardings per key per batch
        was pure host overhead on the input path."""
        tokens = self._stack_shardings["tokens"]
        replicated = self._stack_shardings["replicated"]
        return {
            k: jax.device_put(v, tokens if k in self._RESERVED else replicated)
            for k, v in stack.items()
        }

    def _build_train_step(self):
        if self.mesh_ctx.pp > 1:
            return self._build_pp_train_step()
        use_dropout = self.peft is not None and self.peft.dropout > 0.0
        if self.peft is not None:
            from automodel_tpu.peft.lora import lora_merged_loss

            split_loss = lora_merged_loss(
                lambda merged, fr, b, n: self._forward_loss(
                    {**fr["frozen"], **merged}, b, n),
                lambda fr: fr["lora_base"], self.peft, use_dropout,
            )
        else:
            def split_loss(trainable, frozen, batch, num_label_tokens):
                return self._forward_loss(
                    {**frozen["frozen"], **trainable}, batch, num_label_tokens
                )

        self._step_needs_rng = use_dropout
        step = make_train_step(split_loss, self.optimizer, with_frozen=True,
                               pass_rng=use_dropout)
        return jit_train_step(step, self.train_params, self.opt_state)

    def _build_pp_train_step(self):
        """vlm x pp (reference pipelines the wrapped VLM module the same way,
        infrastructure.py:303): the vision tower + embed merge run per microbatch
        in plain GSPMD (lax.map — one microbatch's vision activations at a
        time), the TEXT layer stack pipelines over pp via the shared dense
        hidden-states pipeline, and the head+CE close outside the manual region.
        Wired for families exposing ``merged_embeds`` over a standard dense text
        stack (LLaVA lineage); mrope/deepstack families (qwen-vl, kimi, omni)
        interleave vision state into the layer stream and stay fenced."""
        from automodel_tpu.parallel.pipeline import (
            _make_head_loss, make_dense_decoder_pp_hidden,
        )
        from automodel_tpu.training.train_step import make_pp_train_step

        model = self.model
        self._check_pp_support()
        cfg_t = model.config.text
        backend = model.backend
        dtype = backend.jnp_dtype
        virtual = int(self.cfg.get("distributed.pp_virtual_stages", 1))
        # honors loss_name (linear_ce for big-vocab VLMs — the scale pp exists
        # for); additive per-microbatch contract, divided by n below
        head_loss = _make_head_loss(cfg_t, dtype, self.loss_name)

        if not hasattr(model, "merged_embeds"):
            # mrope/deepstack families (qwen3-vl): the model owns the pipelined
            # hidden path (vision per microbatch outside the manual region,
            # deepstack features riding the ring — qwen3_vl_moe.make_pp_hidden)
            vl_hidden = model.make_pp_hidden(
                self.mesh, self.rules, seq_len_hint=self.seq_len,
                circular_repeats=virtual,
            )

            def pp_core(full, batch_stack, n):
                h_stack, aux_loss, extras = vl_hidden(full, batch_stack, n)
                other = {k: v for k, v in full.items()
                         if k not in ("moe_layers", "visual")}
                losses = jax.lax.map(
                    lambda args: head_loss(other, {"h": args[0]}, {"labels": args[1]}),
                    (h_stack, batch_stack["labels"]),
                )
                return losses.sum() / n + aux_loss, extras
        else:
            hidden_fn = make_dense_decoder_pp_hidden(
                cfg_t, backend, self.mesh, circular_repeats=virtual
            )

            def pp_core(full, batch_stack, n):
                lm = full["language_model"]

                def embed_mb(mb):
                    return model.merged_embeds(
                        full, mb["input_ids"], mb.get("pixel_values"), self.rules)

                embed_keys = {
                    k: batch_stack[k] for k in ("input_ids", "pixel_values")
                    if k in batch_stack
                }
                x_stack = {
                    "h": jax.lax.map(embed_mb, embed_keys),
                    "positions": batch_stack["positions"],
                    "segment_ids": batch_stack["segment_ids"],
                }
                h_stack = hidden_fn(lm["layers"], x_stack)
                losses = jax.lax.map(
                    lambda args: head_loss(lm, {"h": args[0]}, {"labels": args[1]}),
                    (h_stack, batch_stack["labels"]),
                )
                return losses.sum() / n

        use_dropout = self.peft is not None and self.peft.dropout > 0.0
        if self.peft is not None:
            from automodel_tpu.peft.lora import lora_merged_loss

            split_loss = lora_merged_loss(
                lambda merged, fr, bs, n: pp_core({**fr["frozen"], **merged}, bs, n),
                lambda fr: fr["lora_base"], self.peft, use_dropout,
            )
        else:
            def split_loss(trainable, frozen, batch_stack, n):
                return pp_core({**frozen["frozen"], **trainable}, batch_stack, n)

        self._step_needs_rng = use_dropout
        step = make_pp_train_step(split_loss, self.optimizer, with_frozen=True,
                                  guard_nonfinite=self._check_nan_grads,
                                  pass_rng=use_dropout)
        return jit_train_step(step, self.train_params, self.opt_state)

    @property
    def _frozen_arg(self):
        frozen = {"frozen": self.frozen_params}
        if self.peft is not None:
            frozen["lora_base"] = self.lora_base
        return frozen

    def run_train_validation_loop(self):
        jitted = self._train_step
        # the base loop's peft extra is replaced by _frozen_arg (the VLM step
        # threads its own frozen/base trees); its trailing dropout rng passes
        self._train_step = lambda p, o, stack, *extra: jitted(
            p, o, stack, self._frozen_arg,
            *((extra[-1],) if self._step_needs_rng else ()),
        )
        super().run_train_validation_loop()
        # reassemble the full tree for saves/consumers
        if self.peft is not None:
            from automodel_tpu.peft.lora import merge_lora_params

            merged = merge_lora_params(self.lora_base, self.train_params, self.peft)
            self.params = {**self.frozen_params, **merged}
        else:
            self.params = {**self.frozen_params, **self.train_params}

    def _run_validation(self, step: int):
        if self._eval_step is None:
            from automodel_tpu.training.train_step import make_eval_step

            if self.peft is not None:
                from automodel_tpu.peft.lora import merge_lora_params

                eval_loss = lambda t, f, b, n: self._forward_loss(
                    {**f["frozen"], **merge_lora_params(f["lora_base"], t, self.peft)},
                    b, n, training=False,
                )
            else:
                eval_loss = lambda t, f, b, n: self._forward_loss(
                    {**f["frozen"], **t}, b, n, training=False
                )
            self._eval_step = jax.jit(make_eval_step(eval_loss, with_frozen=True))
        total, count = 0.0, 0
        for batch in self._iter_val_batches():
            n = int((batch["labels"] != -100).sum())
            total += float(self._eval_step(self.train_params, batch, n, self._frozen_arg)) * n
            count += n
        self._log_val_loss(step, total, count)

    def _save(self, step: int, consolidated: bool | None = None):
        # ``consolidated`` matches the base signature: the inherited preemption
        # path passes it to drop the HF export under a short grace window
        self._last_saved_step = step
        client = {
            "rng": self.rng,
            "step_scheduler": self.step_scheduler,
            "dataloader": self.dataloader,
            "resilience": self.resilience,
            "frozen_keys": list(self.frozen_keys),
        }
        if self._pipeline is not None:
            # prefetch: checkpoint the consumed-position snapshots, not the
            # worker-advanced live scheduler/dataloader (train_ft._save)
            client.update(self._pipeline.client_states())
        if self.peft is not None:
            from automodel_tpu.peft.lora import merge_lora_params

            merged = merge_lora_params(self.lora_base, self.train_params, self.peft)
            full = {**self.frozen_params, **merged}
        else:
            full = {**self.frozen_params, **self.train_params}
        self.checkpointer.save(
            step, self.train_params, self.opt_state, client_states=client,
            hf_params=full, consolidated=consolidated,
        )
        self.resilience.record_checkpoint(step)


def main(cfg: ConfigNode | None = None, argv=None):
    if cfg is None:
        cfg = parse_args_and_load_config(argv)
    recipe = FinetuneRecipeForVLM(cfg)
    recipe.setup()
    recipe.run_train_validation_loop()
    return recipe


if __name__ == "__main__":
    main()
