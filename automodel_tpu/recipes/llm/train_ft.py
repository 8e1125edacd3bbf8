"""LLM finetune/pretrain recipe
(reference TrainFinetuneRecipeForNextTokenPrediction, recipes/llm/train_ft.py:803).

The YAML contract mirrors the reference's:

.. code-block:: yaml

    seed: 42
    model:
      pretrained_model_name_or_path: /path/to/hf_dir    # or config: {...} for scratch
    distributed:
      dp_shard: -1    # mesh axes; -1 infers
      tp: 1
      cp: 1
    backend:
      attention: xla
      remat_policy: none
    dataset:
      _target_: automodel_tpu.data.llm.mock.MockSFTDataset
      ...
    step_scheduler: {grad_acc_steps: 1, ckpt_every_steps: 0, max_steps: 50, num_epochs: 1}
    optimizer: {lr: 1.0e-5, weight_decay: 0.0, betas: [0.9, 0.95], max_grad_norm: 1.0}
    lr_scheduler: {lr_warmup_steps: 10, lr_decay_style: cosine}
    packed_sequence: {packed_sequence_size: 0}
    micro_batch_size: 2
    seq_len: 512
    checkpoint: {enabled: false, checkpoint_dir: ckpts, save_consolidated: false}
    validation_dataset: {...}   # optional

Differences from the reference are all TPU-native: one jitted train step owns
grad-accum + collectives (SURVEY.md §7 table), params are sharded by logical-axis
rules rather than module wrappers, and resume restores directly into shardings.
"""

from __future__ import annotations

import contextlib
import logging
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.config.loader import ConfigNode
from automodel_tpu.config.cli_overrides import parse_args_and_load_config
from automodel_tpu.checkpoint.checkpointing import Checkpointer, CheckpointingConfig
from automodel_tpu.checkpoint.reshard import build_topology
from automodel_tpu.data.collate import sft_collate, stack_batches
from automodel_tpu.data.loader import DataLoader
from automodel_tpu.loggers.log_utils import setup_logging
from automodel_tpu.loggers.metric_logger import MetricLogger
from automodel_tpu.models.auto import AutoModelForCausalLM, load_hf_config
from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.observability import Observability, compile_cache
from automodel_tpu.optim import build_lr_schedule, build_optimizer
from automodel_tpu.ops.losses import linear_cross_entropy, masked_cross_entropy
from automodel_tpu.parallel.init import initialize_distributed
from automodel_tpu.parallel.mesh import MeshContext, default_sharding_rules
from automodel_tpu.training.rng import StatefulRNG
from automodel_tpu.training.step_scheduler import StepScheduler
from automodel_tpu.training.train_step import (
    count_label_tokens,
    jit_train_step,
    make_train_step,
)

logger = logging.getLogger(__name__)

__all__ = ["TrainFinetuneRecipeForNextTokenPrediction", "main"]


class _HostMetrics:
    """One step's small metrics on the host. The first reader pays ONE
    ``jax.device_get`` of the tree, under the span ``loss_pull`` (it waits for the
    step's device work: bucket ``device_step``); every later reader of the
    iteration gets the same copies."""

    # per-layer trees with a cadence and a reader of their own (observability/dynamics.py)
    _LEFT_ON_DEVICE = ("dynamics", "nonfinite_map")

    def __init__(self, obs, step: int, metrics: dict):
        self._obs, self._step, self._metrics = obs, step, metrics
        self._host: dict | None = None

    def __call__(self) -> dict:
        if self._host is None:
            with self._obs.track("loss_pull", step=self._step, bucket="device_step"):
                self._host = jax.device_get(
                    {k: v for k, v in self._metrics.items() if k not in self._LEFT_ON_DEVICE})
        return self._host


class TrainFinetuneRecipeForNextTokenPrediction:
    # class-level defaults: subclasses (KD, VLM, ...) override _build_train_step
    # without necessarily setting these
    _pre_qat_step = None
    _qat_start_step = 0
    _step_needs_rng = False
    _dynamics = False  # set by _build_train_step when the dynamics pillar is on
    _run_header: dict | None = None  # built in setup, written by _write_run_header
    # static per-run fields a subclass wants appended to every training.jsonl row
    # (the KD recipe logs kd_ratio/temperature per row, reference kd.py:456)
    _static_log_fields: dict = {}

    def __init__(self, cfg: ConfigNode):
        self.cfg = cfg

    # ------------------------------------------------------------------ setup
    def setup(self):
        self._check_nan_grads = bool(self.cfg.get("distributed.check_for_nan_in_grad", False))
        cfg = self.cfg
        setup_logging(cfg.get("log_level", "INFO"))
        # events fired before the metric loggers exist (restore-time elastic/
        # unverified events during _maybe_resume, anything the observability
        # sinks ahead of them) buffer here; flushed once the loggers come up
        self._deferred_events: list[tuple[int, dict]] = []
        out_dir = cfg.get("output_dir", None)
        if out_dir is None:
            from automodel_tpu.utils.run_dir import default_output_dir

            out_dir = default_output_dir("train")
        os.makedirs(out_dir, exist_ok=True)
        self.output_dir = out_dir  # one resolved dir for every artifact writer
        # observability (docs/observability.md) exists before the work it should
        # see: goodput accounting, HBM + compile telemetry, stall watchdog,
        # on-demand profiling, and the set-up spans below, which tile this
        # function in the order it runs (`setup_summary` row). Events fan out
        # through the same JSONL/wandb/mlflow sinks as step metrics.
        obs = self.observability = Observability.from_config(
            cfg.get("observability"), out_dir, metric_sink=self._log_event
        )
        from automodel_tpu.ops import kernels

        kernels.reset()  # the run header reports THIS run's kernel choices
        # persistent XLA compile cache (warm restart, docs/resilience.md): must
        # be configured before the FIRST compile of the process — the jit model
        # init a few lines down already writes/reads cache entries
        compile_cache.configure(cfg.get("compile_cache"))
        with obs.track("setup_mesh"):
            self._setup_mesh()
        with obs.track("setup_model"):
            self._setup_model()
        with obs.track("setup_data"):
            self._setup_data()
        with obs.track("setup_optimizer"):
            self._setup_optimizer()
        self._select_loss()
        with obs.track("setup_checkpoint"):
            self._setup_checkpoint()
        with obs.track("setup_loggers"):
            self._setup_loggers()
        with obs.track("setup_step_fn"):
            # the jitted step
            self._train_step = self._build_train_step()
            self._eval_step = None  # VLM/seq-cls overrides use the single-slot form
            self._eval_steps = {}  # base: keyed by qat-active (delayed-start switch)
        obs.setup_done()
        return self

    def _setup_mesh(self):
        """Distributed runtime, mesh, sharding rules, the batch stacks' shardings."""
        cfg = self.cfg
        self.dist = initialize_distributed(auto=bool(cfg.get("distributed.auto_init", False)))
        self.observability.bind_process()
        self.rng = StatefulRNG(seed=int(cfg.get("seed", 42)))

        # mesh + sharding rules
        dist_cfg = {k: v for k, v in (cfg.get("distributed") or ConfigNode()).items()
                    if k in ("pp", "dp_replicate", "dp_shard", "ep", "cp", "tp")}
        self.mesh_ctx, self.mesh = self._build_mesh(dist_cfg)
        self.rules = default_sharding_rules(
            sequence_parallel=bool(cfg.get("distributed.sequence_parallel", True)),
        ).with_mesh(self.mesh)
        logger.info("mesh: %s", dict(self.mesh.shape))

        # batch-stack shardings, built once and reused by every device_put
        # (and by the device prefetcher) instead of per key per batch
        self._stack_shardings = self._build_stack_shardings()
        # live only while a train pass runs; _save consults it so checkpoints
        # under prefetch carry the consumed-position scheduler/dataloader state
        self._pipeline = None

    def _setup_model(self):
        """Backend, the model and its parameters (the jitted init), adapters."""
        # backend + model + params
        backend_cfg = self.cfg.get("backend")
        self.backend = BackendConfig(**backend_cfg.to_dict()) if backend_cfg else BackendConfig()
        self._build_model_and_params()
        self._build_peft()

    def _setup_data(self):
        """Tokenizer, both dataloaders and the step scheduler that walks them."""
        cfg = self.cfg
        # tokenizer (optional for mock data)
        self.tokenizer = self._build_tokenizer()

        # data
        self.micro_batch_size = int(cfg.get("micro_batch_size", 1))
        self.seq_len = int(cfg.get("seq_len", 1024))
        global_batch = self.micro_batch_size * jax.process_count()
        if global_batch % self.mesh_ctx.dp_size != 0:
            raise ValueError(
                f"micro_batch_size*processes = {global_batch} must divide by the data-"
                f"parallel degree dp_replicate*dp_shard*ep = {self.mesh_ctx.dp_size}"
            )
        self.dataloader = self._build_dataloader(cfg.get("dataset"), is_train=True)
        val_cfg = cfg.get("validation_dataset")
        self.val_dataloader = self._build_dataloader(val_cfg, is_train=False) if val_cfg else None
        # unsized validation streams would hang the val loop without a bound
        self.max_val_batches = cfg.get("validation_max_batches")
        if self.max_val_batches is not None:
            self.max_val_batches = int(self.max_val_batches)
        elif self.val_dataloader is not None and self.val_dataloader.num_batches is None:
            raise ValueError(
                "streaming (unsized) validation datasets need validation_max_batches: "
                "the validation loop would otherwise never terminate"
            )

        # step scheduler
        ss = (cfg.get("step_scheduler") or ConfigNode()).to_dict()
        ss.setdefault("grad_acc_steps", 1)
        if not getattr(self.dataloader, "_sized", True) and not ss.get("max_steps"):
            raise ValueError(
                "streaming (unsized) datasets need step_scheduler.max_steps: "
                "epoch length is unknown, so num_epochs cannot bound training"
            )
        self.step_scheduler = StepScheduler(dataloader=self.dataloader, **ss)

    def _setup_optimizer(self):
        """Schedule, optimizer and its state, born sharded."""
        cfg = self.cfg
        # optimizer + schedule
        opt_cfg = (cfg.get("optimizer") or ConfigNode()).to_dict()
        lr_cfg = (cfg.get("lr_scheduler") or ConfigNode()).to_dict()
        max_lr = float(opt_cfg.pop("lr", 1e-5))
        # decay horizon is in OPTIMIZER steps: microbatches / grad_acc_steps
        n_batches = self.dataloader.num_batches
        sched = self.step_scheduler
        if n_batches is None:  # unsized stream: max_steps guarded in _setup_data
            total_steps = sched.max_steps
        else:
            steps_per_epoch = max(n_batches // int(sched.grad_acc_steps), 1)
            total_steps = sched.max_steps or (steps_per_epoch * int(sched.num_epochs))
        lr_cfg.setdefault("lr_decay_steps", total_steps)
        self.lr_schedule = build_lr_schedule(max_lr=max_lr, **lr_cfg)
        betas = opt_cfg.pop("betas", (0.9, 0.95))
        if opt_cfg.get("optimizer") == "dion" and self.peft is None:
            # layout-driven matrix canonicalization (head-split dims merge into the
            # true matmul matrix); under PEFT the adapter tree has its own paths and
            # dion falls back to the name heuristic
            opt_cfg.setdefault("logical_axes", self.model.logical_axes())
        self.optimizer = build_optimizer(
            lr=self.lr_schedule, betas=tuple(betas), **opt_cfg
        )
        from automodel_tpu.parallel.sharding_utils import make_sharded_init

        with self.mesh:
            # moments born sharded like their params; scalars replicated. Under PEFT
            # the optimizer tracks only the rank-r adapter tree (reference freezes the
            # base via requires_grad, _peft/lora.py:335; here it is simply not an
            # optimizer argument).
            self.opt_state = make_sharded_init(self.optimizer, self.train_params, self.mesh)(
                self.train_params
            )

    def _select_loss(self):
        """Which loss the step asks for, and how loudly MoE balance is logged."""
        # loss selection (reference build_loss_fn, train_ft.py:345). Big-vocab
        # models default to the fused linear CE (reference defaults to
        # cut-cross-entropy for the same reason, loss/linear_ce.py:119): the
        # (tokens, vocab) logits tensor would otherwise dominate HBM.
        cfg = self.cfg
        default_loss = "masked_ce"
        if (
            getattr(self.model.config, "vocab_size", 0) >= 65536
            and self.mesh_ctx.pp == 1
            and self._moe_config is None
        ):
            default_loss = "linear_ce"
        self.loss_name = cfg.get("loss.name", default_loss)
        self.loss_impl = self._resolve_kernel_requests(cfg.get("loss.impl", "auto"))
        self.loss_filter_eps = cfg.get("loss.filter_eps", 1e-7)
        # MoE load-balance metric logging (reference MoEMetricsConfig, moe/config.py:72)
        self.moe_metrics_mode = cfg.get(
            "moe_metrics.mode", "brief" if self._moe_config is not None else None
        )
        if not cfg.get("moe_metrics.enabled", True):
            self.moe_metrics_mode = None

    def _setup_checkpoint(self):
        """Checkpointer, resilience, and the resume (the `restore` span)."""
        cfg = self.cfg
        # checkpointing
        ck = (cfg.get("checkpoint") or ConfigNode()).to_dict()
        self.checkpointer = Checkpointer(
            CheckpointingConfig(**ck),
            state_dict_adapter=self.model.state_dict_adapter(),
            hf_config=getattr(self, "hf_config", None),
        )
        # resilience (docs/resilience.md): anomaly rollback, verified fallback
        # restore, coordinated preemption, chaos injection. Built before resume
        # (resume goes through the verified-restore path) with a late-bound
        # metric sink — the loggers come up a few lines below, before any event
        # can fire.
        from automodel_tpu.resilience import ResilienceManager

        self.resilience = ResilienceManager.from_config(
            cfg.get("resilience"), checkpointer=self.checkpointer,
            metric_sink=lambda step, **f: self._log_event(step, **f),
        )
        self.chaos = self.resilience.chaos
        # elastic-topology protocol (checkpoint/reshard.py): every save records
        # the saving mesh/pod shape, and restore-time events (elastic_restore,
        # unverified_restore) ride the resilience metric stream
        self.checkpointer.topology = build_topology(self.mesh_ctx)
        self.checkpointer.event_sink = self.resilience.emit
        self._maybe_resume()

    def _setup_loggers(self):
        """Metric and experiment loggers; what the observability binds late
        (mesh axes, cell, the analytic memory plan); the run header."""
        # metrics: JSONL always on; wandb/mlflow when configured (reference
        # train_ft.py:694,1024-1034)
        cfg, out_dir = self.cfg, self.output_dir
        # kill/hang chaos sentinels must survive the restart they cause, so
        # their fired-marks live with the run's other artifacts
        if self.chaos is not None:
            self.chaos.state_dir = out_dir
        self.metric_logger = MetricLogger(os.path.join(out_dir, "training.jsonl"))
        self.val_metric_logger = MetricLogger(os.path.join(out_dir, "validation.jsonl"))
        from automodel_tpu.loggers.experiment_loggers import build_experiment_loggers

        self.experiment_loggers = build_experiment_loggers(cfg)
        # restore-time events buffered before the loggers existed land now, in
        # order, ahead of any step row
        for ev_step, ev_fields in self._deferred_events:
            self._log_event(ev_step, **ev_fields)
        self._deferred_events.clear()

        # axis sizes let the compile-cost row attribute collective bytes to
        # ep/dp/tp/pp (and the roofline grow its moe_a2a bound category)
        self.observability.mesh_axes = {
            str(name): int(size) for name, size in self.mesh.shape.items()
        }
        # identifies this run's cell in signals.json;
        # same model-id fallback chain as the run header below
        _arch = None
        if isinstance(getattr(self, "hf_config", None), dict):
            _arch = (self.hf_config.get("architectures") or [None])[0]
        self.observability.cell_info = {
            "model": str(cfg.get("model.pretrained_model_name_or_path")
                         or _arch or "scratch"),
            "seq_len": int(self.seq_len),
        }
        # analytic HBM plan: the sharded params/opt_state give exact per-shard
        # bytes and the config gives batch/activation estimates, so the
        # headroom/fits verdict exists BEFORE the first compile; compile_step
        # later reconciles it against the compiled step's memory_analysis()
        from automodel_tpu.observability.memory_plan import build_memory_plan

        try:
            self.observability.memory_plan = build_memory_plan(
                self.train_params, self.opt_state,
                micro_batch_size=self.micro_batch_size, seq_len=self.seq_len,
                grad_acc_steps=int(self.step_scheduler.grad_acc_steps),
                dp_degree=self.mesh_ctx.dp_size,
                model_config=getattr(self, "hf_config", None) or self.model.config,
                hbm_limit_override_gib=self.observability.config.hbm_limit_gib,
            )
        except Exception:
            logger.warning("analytic memory plan failed (run continues)",
                           exc_info=True)
        # moe/* telemetry rows (routing entropy, utilization spread, dropped
        # tokens, aux-loss trend); None on dense runs
        from automodel_tpu.observability.moe_stats import MoEStats, local_expert_coords

        self._moe_stats = MoEStats() if self.moe_metrics_mode is not None else None
        # this host's ep-shard coordinates: each host samples the utilization
        # of its OWN experts so the aggregator can name a hot_expert_host
        self._local_ep_coords = (
            local_expert_coords(self.mesh) if self._moe_stats is not None else None
        )
        # per-log-row MFU needs the analytic FLOPs formula; families outside
        # the formula table (VLM towers, audio) skip gracefully
        try:
            from automodel_tpu.utils.flops import flops_per_token

            self._flops_per_token = float(flops_per_token(self.hf_config, self.seq_len))
        except Exception:
            self._flops_per_token = None
        self._device_kind = jax.devices()[0].device_kind

        # self-describing stream: one header row up front (git sha, versions,
        # mesh axis sizes, model id, config digest) so any training.jsonl can
        # be joined to a bench baseline without its YAML
        from automodel_tpu.loggers.metric_logger import build_run_header

        arch = None
        if isinstance(getattr(self, "hf_config", None), dict):
            arch = (self.hf_config.get("architectures") or [None])[0]
        model_id = cfg.get("model.pretrained_model_name_or_path") or arch or "scratch"
        plan = self.observability.memory_plan
        # written by _write_run_header once the first step has been traced, so
        # that it can say which kernels the step really got
        self._run_header = build_run_header(
            cfg=cfg, mesh=self.mesh, model_id=model_id, seq_len=self.seq_len,
            # persistent-XLA-cache config + hit/miss traffic from the
            # model-init compiles (run totals land in compile_summary)
            compile_cache=compile_cache.snapshot(),
            # the fit-before-run verdict: a header reader (or a human tailing
            # the stream) sees whether this config fits its chip before step 0
            **(plan.header_row() if plan is not None else {}),
        )
    def _build_mesh(self, dist_cfg: dict):
        """(MeshContext, Mesh) over every device of the process."""
        ctx = MeshContext(**dist_cfg)
        return ctx, ctx.build_mesh()

    def _resolve_kernel_requests(self, loss_impl: str) -> str:
        """Settle, at setup, the kernel choices that depend on the mesh.

        The fused-CE and grouped-expert kernels run on a device-local view and
        the TPU compiler partitions no Mosaic kernel, so under a multi-device
        mesh ``loss.impl: auto`` takes the XLA blockwise CE (it partitions
        cleanly) and says so; an explicit ``pallas`` there is an error, not a
        quiet switch. Interpret mode (off the TPU) lowers to plain XLA ops and
        stays as it was. Returns the loss impl the step will ask for."""
        from automodel_tpu.ops import kernels

        n = self.mesh.size
        compiled_on_mesh = n > 1 and not kernels.interpret_mode()
        if loss_impl == "auto" and self.loss_name == "linear_ce" and not kernels.kernel_usable(
            "loss", requested="pallas", fallback="xla",
            needs=((n == 1, f"the mesh has {n} devices and the fused CE "
                    "kernel is not partitioned"),),
        ):
            loss_impl = "xla"
        for what, asked in (
            ("loss.impl: pallas", loss_impl == "pallas" and self.loss_name == "linear_ce"),
            ("backend.experts_backend: pallas",
             self.backend.experts_backend == "pallas" and self._moe_config is not None
             and self.backend.dispatcher != "a2a"),
        ):
            if asked and compiled_on_mesh:
                raise kernels.KernelResolutionError(
                    f"{what} on a {n}-device mesh: the TPU compiler partitions no "
                    "Mosaic kernel and this call site has no shard_map; ask for "
                    "the XLA implementation (loss.impl: xla / experts_backend: "
                    "ragged_dot)"
                )
        return loss_impl

    def _build_model_and_params(self):
        cfg = self.cfg
        pretrained = cfg.get("model.pretrained_model_name_or_path")
        # fp32 master params by default (the reference's mixed-precision contract);
        # "bfloat16" = pure-bf16 training — halves params+grads HBM, the trade
        # benchmark / memory-bound configs take
        params_dtype = jnp.dtype(cfg.get("model.params_dtype", "float32"))
        with self.mesh:
            if pretrained:
                self.hf_config = load_hf_config(pretrained)
                self.model, self.params = AutoModelForCausalLM.from_pretrained(
                    pretrained, backend=self.backend, dtype=params_dtype, rules=self.rules
                )
            else:
                model_cfg = cfg.get("model.config")
                if model_cfg is None:
                    raise ValueError("config needs model.pretrained_model_name_or_path or model.config")
                self.hf_config = model_cfg.to_dict() if isinstance(model_cfg, ConfigNode) else dict(model_cfg)
                self.model = AutoModelForCausalLM.from_config(self.hf_config, backend=self.backend)
                axes = self.model.logical_axes()
                shardings = self.rules.tree_sharding(axes)
                init_fn = jax.jit(
                    lambda k: self.model.init(k, params_dtype), out_shardings=shardings
                )
                self.params = init_fn(self.rng.key("model_init"))
        n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(self.params))
        logger.info("model: %s (%.1fM params)", type(self.model).__name__, n_params / 1e6)

    def _build_peft(self):
        """LoRA/DoRA adapter tree (reference apply_lora_to_linear_modules,
        _peft/lora.py:335): self.train_params is what the optimizer and checkpointer
        see — the adapter under PEFT, the full params otherwise."""
        peft_cfg = self.cfg.get("peft")
        self.peft = None
        self.train_params = self.params
        if peft_cfg is None:
            return
        from automodel_tpu.peft.lora import (
            PeftConfig, count_lora_params, init_lora_params, lora_logical_axes,
            merge_lora_params,
        )

        self.peft = PeftConfig.from_dict(peft_cfg.to_dict())
        axes = self.model.logical_axes()
        host_lora = init_lora_params(self.params, axes, self.peft, self.rng.key("lora_init"))
        shardings = self.rules.tree_sharding(lora_logical_axes(axes, self.peft))
        self.train_params = jax.tree.map(jax.device_put, host_lora, shardings)
        # QLoRA (reference quantization/qlora.py): store the adapted base weights
        # int8/nf4 at rest; merge dequantizes transiently inside the step. Must run
        # AFTER lora init (DoRA magnitudes need the dense weights).
        qlora_scheme = peft_cfg.get("qlora")
        if qlora_scheme:
            from automodel_tpu.peft.lora import match_lora_paths
            from automodel_tpu.quantization.qlora import quantize_params, tree_nbytes

            matched = match_lora_paths(axes, self.peft)  # path -> (n_stack, split)
            before = tree_nbytes(self.params)
            self.params = quantize_params(self.params, matched, qlora_scheme)
            logger.info(
                "qlora(%s): base %.1fMB -> %.1fMB (%d tensors quantized)",
                qlora_scheme, before / 2**20, tree_nbytes(self.params) / 2**20, len(matched),
            )
        # one compiled merge reused by every consolidated save
        self._merge_lora = jax.jit(lambda base, lora: merge_lora_params(base, lora, self.peft))
        logger.info(
            "peft: lora dim=%d alpha=%d dora=%s — %.2fM trainable params",
            self.peft.dim, self.peft.alpha, self.peft.use_dora,
            count_lora_params(self.train_params) / 1e6,
        )

    def _build_tokenizer(self):
        tok_cfg = self.cfg.get("tokenizer")
        pretrained = self.cfg.get("model.pretrained_model_name_or_path")
        if tok_cfg and "_target_" in tok_cfg:
            return tok_cfg.instantiate()
        path = (tok_cfg or ConfigNode()).get("pretrained_model_name_or_path") or pretrained
        if path and os.path.exists(os.path.join(path, "tokenizer_config.json")):
            from automodel_tpu.models.auto_tokenizer import AutoTokenizer

            return AutoTokenizer.from_pretrained(path)
        return None

    def _build_dataloader(self, ds_cfg, is_train: bool):
        if ds_cfg is None:
            raise ValueError("config needs a dataset section")
        kwargs = {}
        if self.tokenizer is not None:
            kwargs["tokenizer"] = self.tokenizer
        try:
            dataset = ds_cfg.instantiate(**kwargs)
        except TypeError:
            dataset = ds_cfg.instantiate()  # dataset doesn't take a tokenizer (mock)
        pad_id = 0
        if self.tokenizer is not None and getattr(self.tokenizer, "pad_token_id", None) is not None:
            pad_id = self.tokenizer.pad_token_id
        dataset, collate = self._wrap_dataset_and_collate(dataset, pad_id)
        return DataLoader(
            dataset,
            batch_size=self.micro_batch_size * jax.process_count(),
            collate_fn=collate,
            seed=int(self.cfg.get("seed", 42)),
            shuffle=is_train,
            process_index=jax.process_index(),
            process_count=jax.process_count(),
        )

    def _wrap_dataset_and_collate(self, dataset, pad_id: int):
        """Hook: per-recipe dataset wrapping + collate choice (seq-cls overrides
        this to swap in class-label collation; the base handles packing)."""
        # sequence packing (reference packed_sequence section, train_ft.py:402): each
        # example becomes a fixed-size pack, segment ids carry the boundaries
        pack_size = int(self.cfg.get("packed_sequence.packed_sequence_size", 0))
        if pack_size > 0:
            from automodel_tpu.data.llm.packed import pack_dataset, packed_collate

            if not self.backend.attention_segments:
                raise ValueError(
                    "packed sequences need segment masking in attention; drop "
                    "backend.attention_segments: false (it is a fast path for "
                    "right-padded UNPACKED batches only)"
                )
            if pack_size % self.mesh_ctx.cp != 0:
                raise ValueError(
                    f"packed_sequence_size {pack_size} must divide by cp={self.mesh_ctx.cp}"
                )
            dataset = pack_dataset(
                dataset,
                pack_size,
                pad_token_id=pad_id,
                max_packs=self.cfg.get("packed_sequence.max_packs"),
                drop_long_samples=bool(self.cfg.get("packed_sequence.drop_long_samples", False)),
            )
            self.seq_len = pack_size
            return dataset, packed_collate
        return dataset, (lambda exs: sft_collate(exs, seq_len=self.seq_len, pad_token_id=pad_id))

    @property
    def _moe_config(self):
        cfg = self.model.config
        return getattr(cfg, "moe", None) or getattr(getattr(cfg, "text", None), "moe", None)

    def _model_forward(self, params, batch, training):
        """The model call; subclasses (VLM) override to thread extra modalities
        while the loss/aux handling below stays shared."""
        kwargs = {}
        if self._moe_config is not None:
            # segment id 0 marks padding (sft_collate contract): pad tokens must not
            # count for routing load, aux loss, or the gate-bias update
            kwargs = {"token_mask": batch["segment_ids"] != 0, "training": training}
        # sharding constraints are pure fusion barriers on a single device
        rules = self.rules if self.mesh.size > 1 else None
        return self.model(
            params, batch["input_ids"], positions=batch["positions"],
            segment_ids=batch["segment_ids"], rules=rules,
            return_hidden=self.loss_name == "linear_ce", **kwargs,
        )

    def _forward_loss(self, params, batch, num_label_tokens, training=True):
        out = self._model_forward(params, batch, training)
        out, stats = out if isinstance(out, tuple) else (out, None)
        with jax.named_scope("lm_head_loss"):  # the model's head opens the same scope
            if self.loss_name == "linear_ce":
                from automodel_tpu.models.common.transformer import resolve_unembed

                # cast to the activation dtype: matches the masked path's logits
                # precision and halves the kernel's VMEM tile footprint; the helper
                # folds tied-embedding fallback + granite logits_scaling in
                mcfg = getattr(self.model.config, "text", self.model.config)
                unembed = resolve_unembed(mcfg, params, out.dtype)
                if unembed is None:
                    raise ValueError("linear_ce: model has neither lm_head nor a tied embedding table")
                loss = linear_cross_entropy(
                    out, unembed, batch["labels"],
                    num_label_tokens, impl=self.loss_impl, filter_eps=self.loss_filter_eps,
                )
            else:
                loss = masked_cross_entropy(out, batch["labels"], num_label_tokens)
        if stats is None:
            return loss
        aux = {"expert_load": stats["expert_load"]}
        if "dropped_token_frac" in stats:
            # a2a dispatch: capacity-overflow rate, summed across microbatches in
            # the step carry -> divide by grad-accum steps at log time
            aux["dropped_token_frac"] = stats["dropped_token_frac"]
        if stats["aux_loss"] is not None:
            # reference scales aux by token count to undo 1/num_label_tokens grad
            # normalization (layers.py:367-372 MoEAuxLossAutoScaler); additive across
            # microbatches this weights each microbatch's aux by its token fraction
            mb_tokens = count_label_tokens(batch["labels"]).astype(jnp.float32)
            loss = loss + self._moe_config.aux_loss_coeff * stats["aux_loss"] * (
                mb_tokens / num_label_tokens
            )
            # unscaled balance loss, token-weighted the same way: summed across
            # microbatches it is the step-level weighted mean for moe/aux_loss
            aux["moe_aux_loss"] = stats["aux_loss"] * (mb_tokens / num_label_tokens)
        return loss, aux

    def _post_update(self):
        """Gate-bias loss-free-balancing hook (reference update_moe_gate_bias,
        train_ft.py:1341): pure param update from the accumulated expert load."""
        moe = self._moe_config
        if moe is None or moe.gate_bias_update_factor <= 0:
            return None
        from automodel_tpu.moe.gate import make_gate_bias_post_update

        return make_gate_bias_post_update(moe.gate_bias_update_factor)

    def _build_train_step(self):
        self._pre_qat_step = None
        self._qat_start_step = 0
        self._step_needs_rng = False
        # resilience keeps params restorable THROUGH an anomaly: the jitted step
        # must zero non-finite updates so the tree the host later rolls back
        # from (or keeps, on skip_update) is never poisoned
        self._guard_nonfinite = self._check_nan_grads or self.resilience.guards_updates
        # the dynamics pillar asks the jitted step for the per-subtree telemetry
        # pytree; the reductions fuse into the step, the host syncs on cadence
        self._dynamics = self.observability.dynamics_enabled
        qfn = self._qat_param_fn()
        qat_cfg = self.cfg.get("qat")
        qat_start = int(qat_cfg.get("fake_quant_after_n_steps") or 0) if qat_cfg else 0

        def build(with_qat: bool):
            """One step builder covering every composition; QAT is a param-level
            transform so it threads through pp / peft / plain identically."""
            q = qfn if (with_qat and qfn is not None) else (lambda p: p)
            if self.mesh_ctx.pp > 1:
                from automodel_tpu.parallel.pipeline import (
                    make_dense_decoder_pp_loss,
                    make_moe_pp_loss,
                )
                from automodel_tpu.training.train_step import make_pp_train_step

                virtual = int(self.cfg.get("distributed.pp_virtual_stages", 1))
                if self._moe_config is not None:
                    pp_loss = make_moe_pp_loss(
                        self.model, self.mesh, self.rules, loss_name=self.loss_name,
                        seq_len_hint=self.seq_len, circular_repeats=virtual,
                    )
                    pp_post_update = self._post_update() if self.peft is None else None
                    if self.peft is not None and self._post_update() is not None:
                        logger.warning("moe gate-bias update disabled under peft (base is frozen)")
                else:
                    pp_loss = make_dense_decoder_pp_loss(
                        self.model, self.mesh, self.rules, loss_name=self.loss_name,
                        circular_repeats=virtual,
                    )
                    pp_post_update = None
                if self.peft is not None:
                    # peft + pp (reference composes them, infrastructure.py:303):
                    # the LoRA merge happens OUTSIDE the pp-manual region in plain
                    # GSPMD — merged layer stacks stay (L, ...) and shard over pp
                    # as usual; grads flow only to the rank-r adapter. qat x peft
                    # x pp: the BASE quantizes before the merge (the adapter
                    # trains in full precision on a quantized base, reference
                    # QLoRA-style qat semantics).
                    from automodel_tpu.peft.lora import lora_merged_loss

                    # dropout rides the merged-delta mask (peft/lora.py:296);
                    # the merge — and thus the mask — happens once per step
                    # outside the pp-manual region (make_pp_train_step docs)
                    use_dropout = self.peft.dropout > 0.0
                    pp_peft_loss = lora_merged_loss(
                        lambda merged, base, bs, n: pp_loss(merged, bs, n),
                        q, self.peft, use_dropout,
                    )
                    self._step_needs_rng = use_dropout
                    return make_pp_train_step(pp_peft_loss, self.optimizer,
                                              guard_nonfinite=self._guard_nonfinite,
                                              with_frozen=True,
                                              pass_rng=use_dropout,
                                              dynamics=self._dynamics)
                # qat x pp: quantize the stacked layer params (and head/embed)
                # BEFORE the manual region — fake-quant is elementwise, GSPMD
                # partitions it over the pp-sharded layer dim like any other op
                return make_pp_train_step(lambda p, bs, n: pp_loss(q(p), bs, n),
                                          self.optimizer,
                                          post_update=pp_post_update,
                                          guard_nonfinite=self._guard_nonfinite,
                                          dynamics=self._dynamics)
            if self.peft is not None:
                from automodel_tpu.peft.lora import lora_merged_loss

                if self._post_update() is not None:
                    logger.warning("moe gate-bias update disabled under peft (base is frozen)")

                use_dropout = self.peft.dropout > 0.0
                peft_loss = lora_merged_loss(
                    lambda merged, base, b, n: self._forward_loss(merged, b, n),
                    q, self.peft, use_dropout,
                )
                self._step_needs_rng = use_dropout
                return make_train_step(peft_loss, self.optimizer, with_frozen=True,
                                       guard_nonfinite=self._guard_nonfinite,
                                       pass_rng=use_dropout,
                                       dynamics=self._dynamics)
            return make_train_step(
                lambda p, b, n: self._forward_loss(q(p), b, n),
                self.optimizer, post_update=self._post_update(),
                guard_nonfinite=self._guard_nonfinite,
                dynamics=self._dynamics,
            )

        step = build(with_qat=True)
        # QAT delayed start (reference qat.py:46 fake_quant_after_n_steps): two
        # compiled steps, python-level switch on the scheduler step — zero
        # per-step overhead vs a lax.cond inside jit. Applies to every
        # composition since build() is uniform.
        if qfn is not None and qat_start > 0:
            self._pre_qat_step = jit_train_step(
                build(with_qat=False), self.train_params, self.opt_state)
            self._qat_start_step = qat_start
        return jit_train_step(step, self.train_params, self.opt_state)

    def _qat_param_fn(self):
        """params -> fake-quantized params, or None when QAT is off.

        The param-level transform is what makes QAT compose: the pp loss, the
        LoRA base, and the plain forward all consume a param tree, so one
        transform serves qat, qat x pp, and qat x peft (reference threads the
        same module-swap through its one sequencing path, infrastructure.py:303).
        Memoized: the path match never changes after setup and validation calls
        this every pass.
        """
        if not hasattr(self, "_qat_fn_memo"):
            self._qat_fn_memo = self._build_qat_param_fn()
        return self._qat_fn_memo

    def _build_qat_param_fn(self):
        qat_cfg = self.cfg.get("qat")
        if qat_cfg is None or not qat_cfg.get("enabled", True):
            return None
        import dataclasses

        from automodel_tpu.peft.lora import PeftConfig as _MatchCfg, match_lora_paths
        from automodel_tpu.quantization.qat import QATConfig, fake_quant_params

        known = {f.name for f in dataclasses.fields(QATConfig)}
        qat = QATConfig(**{k: v for k, v in qat_cfg.to_dict().items() if k in known})
        # fake_quant_after_n_steps is handled by _build_train_step's two-step switch
        matcher = _MatchCfg(target_modules=qat.target_modules,
                            match_all_linear=qat.target_modules == ["*"])
        paths = sorted(match_lora_paths(self.model.logical_axes(), matcher))
        logger.info("qat: int%d fake-quant on %d weight tensors", qat.weight_bits, len(paths))
        return lambda params: fake_quant_params(params, paths, qat)

    def _qat_wrap(self, forward):
        """QAT (reference quantization/qat.py + train_ft.py:1092): fake-quantize
        matched weights in the forward so training sees post-quantization rounding;
        gradients pass straight through."""
        qfn = self._qat_param_fn()
        if qfn is None:
            return forward

        def qat_forward(params, batch, num_label_tokens):
            return forward(qfn(params), batch, num_label_tokens)

        return qat_forward

    def _maybe_resume(self):
        if not self.checkpointer.config.enabled:
            return
        el = self.resilience.config.elastic
        # join/leave: a freshly-joined host has no local checkpoint view and
        # abstains from the pod-agreed restore step instead of forcing a
        # fresh run (checkpoints live on storage every host can reach)
        allow_joiners = bool(el.enabled and el.allow_joiners)
        # the resume's cost is the `restore` span and goodput bucket, not idle.
        # A fresh run looks, finds no step and goes on: that is no restore and
        # bills none (a joiner of a pod may be handed a step it does not see)
        restorable = self.checkpointer.latest_step() is not None or (
            allow_joiners and jax.process_count() > 1)
        with self.observability.track("restore") if restorable else contextlib.nullcontext():
            # verified restore with walk-back: a truncated/corrupt latest step falls
            # back to the newest step that passes its integrity manifest, agreed
            # across hosts (docs/resilience.md). load_latest_verified returns None
            # only when NO restorable checkpoint exists — a fresh run.
            restored = self.checkpointer.load_latest_verified(
                self.train_params, self.opt_state, allow_joiners=allow_joiners)
            if restored is None:
                return
            self.train_params, self.opt_state, client, step = restored
            logger.info("resuming from step %d", step)
            elastic = client.pop("__elastic__", None)
            host_rows = (client.pop("__hosts__", None) or {}).get("dataloader")
            if elastic is not None and el.enabled:
                self._repartition_client_state(client, host_rows, step)
            self._apply_client_state(client)

    def _repartition_client_state(self, client: dict, host_rows, step: int):
        """Elastic resume (docs/resilience.md): Orbax already resharded the
        arrays into the new mesh's templates; what is left is the host state.
        The saved dataloader cursor counts the OLD pod's global batches —
        convert it into this pod's units so no example is double-trained or
        silently dropped across the reshape."""
        from automodel_tpu.resilience.elastic import repartition_dataloader_state

        state = client.get("dataloader")
        if state is None:
            return
        new_state, info = repartition_dataloader_state(
            state, self.dataloader.batch_size, host_rows=host_rows
        )
        client["dataloader"] = new_state
        self._log_event(step, event="elastic_data_repartition", **info)

    def _apply_client_state(self, client: dict):
        """Restore the host-side training services a checkpoint carries; shared
        by process-restart resume and in-process anomaly rollback."""
        if self.peft is None:
            self.params = self.train_params
        if "rng" in client:
            self.rng.load_state_dict(client["rng"])
        if "step_scheduler" in client:
            self.step_scheduler.load_state_dict(client["step_scheduler"])
        if "dataloader" in client:
            self.dataloader.load_state_dict(client["dataloader"])
        if "resilience" in client:
            self.resilience.load_state_dict(client["resilience"])

    def _build_stack_shardings(self) -> dict:
        """Per-stack-key NamedShardings, built once in setup() and reused every
        batch (rebuilding them per key per step was pure host overhead on the
        input path); subclasses with extra modalities add their own entries."""
        return {"tokens": self.rules.sharding((None, "batch", None))}

    def _device_put_stack(self, stack):
        """Shard the stacked (n_micro, B, S) token streams over the batch axes;
        subclasses with extra modalities (VLM media tensors) override per key.
        jax.device_put only *issues* the H2D transfer — under the prefetch
        pipeline the copy overlaps the previous step's compute."""
        sharding = self._stack_shardings["tokens"]
        return {k: jax.device_put(v, sharding) for k, v in stack.items()}

    def _build_input_pipeline(self):
        """Input pipeline for one train pass (docs/performance.md): synchronous
        fetch, or host prefetch thread + device double-buffering behind
        ``dataloader.prefetch``. Rebuilt per pass — a rollback restores
        scheduler/dataloader state, and the worker must restart from there."""
        from automodel_tpu.data.prefetch import InputPipeline, PrefetchConfig

        return InputPipeline(
            scheduler=self.step_scheduler,
            dataloader=self.dataloader,
            stack_fn=stack_batches,
            put_fn=self._device_put_stack,
            config=PrefetchConfig.from_config(self.cfg.get("dataloader.prefetch")),
        )

    def _warmup_step_variants(self, obs, step_fn, exec_fn, stack, extra, step):
        """AOT warmup (docs/resilience.md "warm restart"): pre-compile every
        step shape the scheduler can emit beyond the steady one — today the
        trailing partial-accumulation stack at the epoch tail — into the
        executor's variant table, so no shape demotes to a mid-run jit compile.
        With the persistent compile cache configured, a restarted run's warmup
        deserializes instead of compiling. Warmup stacks are built host-side
        and pushed through the SAME device_put path as real batches so their
        shardings match exactly (device-side slicing could silently differ and
        fake an AOT rejection). Gated by ``compile_cache.warmup`` (default off:
        it fronts the epoch-tail compile cost at step 0)."""
        if not bool(self.cfg.get("compile_cache.warmup", False)):
            return
        from automodel_tpu.resilience.elastic import plan_warmup_micro_counts

        with obs.track("step_variants", step=step):
            for n_micro in plan_warmup_micro_counts(
                self.dataloader.num_batches, self.step_scheduler.grad_acc_steps
            ):
                host_stack = {
                    k: np.zeros((n_micro,) + tuple(v.shape[1:]), dtype=v.dtype)
                    for k, v in stack.items()
                }
                if obs.precompile_variant(
                    exec_fn, step_fn,
                    (self.train_params, self.opt_state,
                     self._device_put_stack(host_stack), *extra),
                    step=step,
                ):
                    logger.info("warmup: pre-compiled trailing %d-microbatch step shape",
                                n_micro)

    def _write_run_header(self):
        """The one run-header row, written when the first train step has been
        traced (or, failing that, at teardown): kernel choices are made at
        trace time, and the header is where a reader looks for them."""
        header, self._run_header = self._run_header, None
        if header is None:
            return
        from automodel_tpu.ops import kernels

        self.metric_logger.log_header(**header, kernels=kernels.snapshot())

    # ------------------------------------------------------------------ train
    def _log_event(self, step: int, **fields):
        """Async structured events (watchdog stalls, resilience rollbacks)
        into the metric fan-out and onto the trace timeline."""
        if getattr(self, "metric_logger", None) is None:
            # restore-time events (elastic_restore, unverified_restore) fire
            # during _maybe_resume, before the loggers exist
            self._deferred_events.append((step, dict(fields)))
            return
        self.metric_logger.log(step, **fields)
        for lg in self.experiment_loggers:
            lg.log(step, **fields)
        obs = getattr(self, "observability", None)
        if obs is not None:
            obs.note_event(step, fields)

    def run_train_validation_loop(self):
        obs = self.observability
        obs.start()
        # compile billing survives rollback re-entries: a restored pass reuses
        # the already-jitted step, so it must not re-charge the compile bucket
        self._compiled_fns: set[int] = set()
        # id(step_fn) -> executor from obs.compile_step (the AOT-compiled
        # object whose costs were extracted; shares no cache with jit)
        self._step_executors: dict[int, Any] = {}
        self._checked_vocab = False
        outcome = "done"
        try:
            with self.mesh:
                # each pass runs until done/preempted or an anomaly rolls state
                # back to the last verifiable checkpoint, in-process; the pass
                # then restarts with a fresh scheduler iterator (same mechanics
                # as a process-restart resume, without losing the jit cache)
                while True:
                    outcome = self._train_pass(obs)
                    if outcome != "rollback":
                        break
            # final checkpoint; wait() commits any async save's latest symlink.
            # A preempted pass already saved under its grace deadline — a second
            # save here would re-run the consolidated export it chose to skip.
            if self.checkpointer.config.enabled:
                with obs.track("checkpoint"):
                    if outcome != "preempted":
                        self._save(self.step_scheduler.step)
                    self.checkpointer.wait()
        except BaseException as exc:
            # OOM flight recorder: when the failure is an allocator
            # exhaustion, harvest the live-buffer census + memory plan +
            # per-device counters into oom_report.json while the buffers
            # still exist, then re-raise — orchestration must still see the
            # original failure
            obs.maybe_dump_oom(exc, step=self.step_scheduler.step)
            raise
        finally:
            self._write_run_header()  # a run that died before its first trace
            # run-total AOT/jit-fallback + compile-cache traffic (the
            # run_header only sees the counts up to the first trace)
            self._log_event(self.step_scheduler.step, event="compile_summary",
                            **obs.compile_summary())
            obs.close()
            self.metric_logger.close()
            self.val_metric_logger.close()
            for lg in self.experiment_loggers:
                lg.close()

    def _train_pass(self, obs) -> str:
        """One pass over the step loop inside the mesh context. Returns
        ``"done"`` (data exhausted / max_steps), ``"preempted"`` (SIGTERM saved
        and exited), or ``"rollback"`` (state restored to the last good
        checkpoint — the caller re-enters). Owns the input pipeline's
        lifecycle: built per pass from the (possibly restored) scheduler
        position, closed on every exit path so no worker thread outlives the
        pass or keeps mutating scheduler/dataloader state."""
        with obs.track("setup_pipeline"):
            self._pipeline = self._build_input_pipeline()
        try:
            return self._run_step_loop(obs)
        finally:
            # a SIGTERM truncation inside the loop may have swapped in a
            # rebuilt pipeline (the original is already closed); close the
            # live one — close() is idempotent
            self._pipeline.close()
            self._pipeline = None

    def _fetch_batch(self, obs, step: int | None):
        """One optimizer step's batch through the pipeline, in the loop's hand and
        not yet consumed; None at end of data. ``step`` is the step it is for."""
        with obs.track("data_wait", step=step):
            while True:
                # synchronous: fetch + collate + stack + device_put inline.
                # prefetched: pops an already-transferred stack — this blocks
                # only when the host worker is behind
                fetched = self._pipeline.get(ahead=True)
                if fetched is not None or not self._pipeline.truncated_by_local_sigterm():
                    return fetched
                # The worker stops on the LOCAL flag only (no collectives
                # off the main thread), so on the signaled host the stream
                # can end with data remaining while the pod has NOT agreed
                # to preempt. Returning "done" here would desync the pod:
                # the other hosts keep stepping and their per-step agreed
                # allgather waits forever while this host runs teardown/
                # final-save collectives — and the grace-window checkpoint
                # is lost. Rebuild from the live scheduler position
                # (exactly the last consumed step) and keep the step
                # rhythm: the next consumed step's agreed check sees this
                # host's flag, so every host takes the preemption save
                # together at the same step. At most one rebuild per
                # signal — the worker always yields >= 1 item before its
                # post-yield flag check, and that step's agreed check
                # returns True pod-wide.
                self._pipeline.close()
                self._pipeline = self._build_input_pipeline()

    def _run_step_loop(self, obs) -> str:
        t_last = time.perf_counter()
        steps_since_log = 0
        window_overhead = 0.0  # eval/ckpt seconds to exclude from step_time_s
        compiled_fns = self._compiled_fns
        last_dyn_row: dict = {}  # latest cadence sample; merged into log rows
        # One batch in hand (docs/performance.md "The step loop's order"): step
        # N+1's batch is fetched right after step N's dispatch, while the device
        # works, so that when N's scalars arrive the host has only to write the
        # row and enqueue N+1. Only a pass's first batch is fetched with the
        # device idle.
        fetched = self._fetch_batch(obs, None)
        ready_ahead = 0  # this step's batch was in hand before the last step's scalars
        while fetched is not None:
            self._pipeline.consume(fetched)
            stack = fetched.stack
            if not self._checked_vocab:
                # tokenizer/model vocab mismatch shows up as NaN loss deep in
                # training; fail loudly on the first batch instead
                vocab = getattr(getattr(self.model.config, "text", self.model.config),
                                "vocab_size", None)
                if vocab is not None:
                    for key in ("input_ids", "q_ids", "p_ids"):
                        if key in stack and int(stack[key].max()) >= vocab:
                            raise ValueError(
                                f"batch {key} contains token id {int(stack[key].max())} "
                                f">= model vocab_size {vocab}: tokenizer/model mismatch"
                            )
                self._checked_vocab = True
            # the consumed step rides on the fetched batch: under prefetch the
            # scheduler's own counter runs ahead (worker thread)
            step = fetched.step
            obs.on_step_start(step)
            extra = (self.params,) if self.peft is not None else ()
            if self._step_needs_rng:
                extra = (*extra, self.rng.key("lora_dropout"))
            step_fn = self._train_step
            if self._pre_qat_step is not None and step < self._qat_start_step:
                step_fn = self._pre_qat_step
            if id(step_fn) not in compiled_fns:
                # first call of a jitted step pays tracing + XLA compile
                # (step 0, and again at a delayed-QAT switch): bill it to
                # the compile bucket and keep it OUT of the throughput
                # window — the first step_time_s/tps row would otherwise
                # absorb minutes of compile.
                #
                # compile_step AOT-compiles BEFORE the first execution (the
                # step donates its params — afterwards the example buffers are
                # gone), extracts HLO costs + the roofline once, and hands
                # back the executor the rest of the run steps through.
                # Its children: `step_lower`, `step_compile`, `step_analysis`
                # (inside compile_step), `first_step`, `step_variants`; the span
                # itself is what `compile_time_s` adds up.
                with obs.track("compile", step=step):
                    exec_fn = obs.compile_step(
                        step_fn, (self.train_params, self.opt_state, stack, *extra),
                        step=step, on_traced=self._write_run_header,
                    )
                    with obs.track("first_step", step=step):
                        self.train_params, self.opt_state, metrics = exec_fn(
                            self.train_params, self.opt_state, stack, *extra
                        )
                        jax.block_until_ready(metrics["loss"])
                    self._write_run_header()  # no AOT executor: traced by the call
                    compiled_fns.add(id(step_fn))
                    self._step_executors[id(step_fn)] = exec_fn
                    # warm restart (docs/resilience.md): pre-compile the other step
                    # shapes the scheduler can emit so none demotes to mid-run jit
                    self._warmup_step_variants(obs, step_fn, exec_fn, stack, extra, step)
                obs.write_setup_summary(step)  # once: the run's first step has finished
                t_last = time.perf_counter()
                steps_since_log = 0  # compile step excluded from the window
                window_overhead = 0.0
            else:
                exec_fn = self._step_executors.get(id(step_fn), step_fn)
                with obs.track("train_step", step=step, bucket="device_step"):
                    self.train_params, self.opt_state, metrics = exec_fn(
                        self.train_params, self.opt_state, stack, *extra
                    )
                steps_since_log += 1
            # the dispatch is asynchronous: until the first read of `metrics`
            # the host does what needs none of step N's scalars, the device busy
            input_ready_ahead, ready_ahead = ready_ahead, 1
            fetched = self._fetch_batch(obs, step + 1)
            is_log_step = self.step_scheduler.is_log_step_at(step)
            if is_log_step:
                with obs.track("lr_schedule", step=step):
                    lr = float(self.lr_schedule(step))  # a host number (optim/scheduler.py)
                # global tokens per optimizer step (local slice x process count);
                # biencoder batches carry q_ids/p_ids instead of input_ids
                step_tokens = sum(
                    int(np.prod(stack[k].shape))
                    for k in ("input_ids", "q_ids", "p_ids") if k in stack
                ) * jax.process_count()
            with obs.track("step_hooks", step=step):
                if self.chaos is not None and self.chaos.should_poison(step):
                    # fault injection (resilience/chaos.py): simulate corruption
                    # the jit guard missed — params AND metrics go non-finite,
                    # so recovery genuinely requires a checkpoint rollback
                    self.train_params, metrics = self.chaos.poison(
                        step, self.train_params, metrics
                    )
                if self.chaos is not None and self.chaos.should_spike(step):
                    # finite-spike injection: one layer's params blow up, metrics
                    # stay clean — the NEXT step's loss z-score and per-layer
                    # dynamics must detect it organically and name the layer
                    self.train_params = self.chaos.spike(step, self.train_params)
                if self.peft is None:
                    self.params = self.train_params
                obs.heartbeat(step)
                # every reader of the step's scalars, here and in the row, shares
                # ONE transfer, made when the first of them asks
                host = _HostMetrics(obs, step, metrics)
                # dynamics pillar (observability/dynamics.py): fold the step's
                # per-subtree telemetry on cadence, run the loss-spike flight
                # recorder, and derive the per-layer attribution (layer_hint) the
                # resilience verdicts and skip/raise events cite
                dyn_row, layer_hint = self._dynamics_host_step(obs, step, metrics, host, stack)
                if dyn_row:
                    last_dyn_row = dyn_row
                if self.resilience.active:
                    # same-step anomaly handling (docs/resilience.md): one
                    # scalar device->host sync per step buys detection before
                    # the bad trajectory reaches the next checkpoint
                    action = self.resilience.on_step(
                        step,
                        float(host()["loss"]),
                        float(host()["grad_norm"]),
                        bool(host().get("nonfinite", False)),
                        layer=layer_hint,
                    )
                    if action == "rollback":
                        # stop the worker BEFORE restoring: it mutates the very
                        # scheduler/dataloader state the rollback rewrites, and the
                        # restore must not race in-flight prefetches; the batch
                        # in hand goes with the pipeline
                        self._pipeline.close()
                        if self._perform_rollback(step, obs):
                            return "rollback"
                        action = "abort"  # nothing verifiable to roll back to
                    if action == "abort":
                        raise RuntimeError(
                            f"resilience: unrecoverable training anomaly at step {step} "
                            f"(loss={float(host()['loss'])}, "
                            f"grad_norm={float(host()['grad_norm'])}"
                            + (f", layer={layer_hint}" if layer_hint else "") + "); "
                            "rollback budget exhausted or no verifiable checkpoint"
                        )
                    # skip_update: the jitted guard already zeroed the bad
                    # update — params/optimizer state are the pre-step values
                elif self._check_nan_grads and bool(host()["nonfinite"]):
                    # reference check_for_nan_in_grad (distributed/config.py:129):
                    # without resilience a non-finite gradient is a training
                    # bug. The jitted step already SKIPPED the corrupt update
                    # (guard_nonfinite), so params and optimizer state stay
                    # clean; raise loudly here every step.
                    raise RuntimeError(
                        f"non-finite training signal at step {step}: "
                        f"loss={float(host()['loss'])} "
                        f"grad_norm={float(host()['grad_norm'])}"
                        + (f" first nonfinite subtree={layer_hint}" if layer_hint else "")
                        + " (the offending update was skipped; params remain clean)"
                    )
            if is_log_step:
                # the transfer blocks on the step's device work, so this wait
                # is device time, not idle (span `loss_pull`, bucket device_step)
                scalars = host()
                loss = float(scalars["loss"])
                gnorm = float(scalars["grad_norm"])
                ntok = int(scalars["num_label_tokens"])
                with obs.track("log_row", step=step):
                    now = time.perf_counter()
                    # per-step time, with eval/ckpt pauses subtracted;
                    # steps_since_log == 0 <=> the window held only a compile
                    # step, whose device time already lives in compile_time_s
                    # — no throughput to report yet
                    dt = (max(now - t_last - window_overhead, 0.0) / steps_since_log
                          if steps_since_log else None)
                    t_last = now
                    steps_since_log = 0
                    window_overhead = 0.0
                    extra = {}
                    moe_max_util = None
                    if "expert_load" in scalars and self.moe_metrics_mode:
                        from automodel_tpu.moe.metrics import (
                            compute_load_balance_metrics,
                            held_row_blocks_share,
                            held_rows_share,
                        )

                        loads = scalars["expert_load"]
                        extra = compute_load_balance_metrics(loads, mode=self.moe_metrics_mode)
                        moe_cfg = self._moe_config
                        if not moe_cfg.holds_all_experts:
                            held = (moe_cfg.first_held_expert, moe_cfg.held_experts)
                            extra["moe_load/held_rows_share"] = held_rows_share(loads, *held)
                            extra["moe_load/held_row_blocks_share"] = held_row_blocks_share(
                                loads, *held, moe_cfg.n_activated_experts)
                    if "dropped_token_frac" in scalars:
                        # summed over the step's microbatches in the train-step carry
                        extra["moe_load/dropped_token_frac"] = float(
                            scalars["dropped_token_frac"]
                        ) / max(1, self.step_scheduler.grad_acc_steps)
                    if self._moe_stats is not None:
                        # the moe/* family: routing entropy, utilization spread,
                        # dropped tokens, aux-loss trend, routed tokens/s/chip
                        extra.update(self._moe_stats.rows(
                            scalars,
                            grad_acc_steps=self.step_scheduler.grad_acc_steps,
                            step_time_s=dt,
                            device_count=jax.device_count(),
                            mode=self.moe_metrics_mode,
                        ))
                        if "expert_load" in scalars:
                            from automodel_tpu.observability.moe_stats import (
                                local_expert_max_util,
                            )

                            moe_max_util = local_expert_max_util(
                                scalars["expert_load"],
                                self._local_ep_coords,
                                self.observability.mesh_axes.get("ep", 1),
                            )
                    row = dict(
                        loss=loss,
                        grad_norm=gnorm,
                        lr=lr,
                        num_label_tokens=ntok,
                        step_time_s=round(dt, 4) if dt else None,
                        tps=round(step_tokens / dt, 1) if dt else None,
                        tps_per_chip=(round(step_tokens / dt / jax.device_count(), 1)
                                      if dt else None),
                        **extra,
                        **self._static_log_fields,
                    )
                    # 1: this step's batch was on the device before the last
                    # step's scalars came (0: a pass's first step)
                    row["input_ready_ahead"] = input_ready_ahead
                    if self._pipeline.prefetching:
                        # stacks buffered ahead of the consumer at log time; a
                        # persistent 0 with high goodput/data_wait = input-bound
                        row["prefetch_depth"] = self._pipeline.ready_depth()
                    if self._flops_per_token is not None:
                        from automodel_tpu.utils.flops import mfu

                        fpt = self._flops_per_token
                        tps_now = step_tokens / dt if dt else None
                        # compile-only window: keys present, no rate yet
                        row["tflops_per_chip"] = round(
                            tps_now * fpt / 1e12 / jax.device_count(), 2
                        ) if dt else None
                        # a CPU has no peak (utils/flops.mfu): its rows carry no mfu
                        util = mfu(tps_now or 0.0, fpt, self._device_kind, jax.device_count())
                        if util is not None:
                            row["mfu"] = round(util, 4) if dt else None
                    if last_dyn_row:
                        # the most recent cadence sample of the per-layer dynamics
                        # telemetry rides the log row (dynamics/<layer>/<metric>)
                        row.update(last_dyn_row)
                    row.update(obs.step_metrics())
                    row.update(obs.roofline_row(dt))
                    # collective on multi-host: every process reaches the log step
                    # (the schedule is deterministic), proc 0 writes the result;
                    # MoE runs gather max expert utilization too (hot_expert_host);
                    # dynamics runs gather the replicated grad_norm so cross-host
                    # disagreement raises divergent_host (replica desync)
                    row.update(obs.host_metrics(
                        dt, moe_max_util=moe_max_util,
                        grad_norm=gnorm if self._dynamics else None))
                    self.metric_logger.log(step, **row)
                    for lg in self.experiment_loggers:
                        lg.log(step, **row)
                    # the same row feeds the OOM flight recorder's ring (context
                    # for a future crash report) and the excursion detector (a
                    # step-time spike beyond the rolling median arms an auto-trace)
                    obs.record_row(step, row)
                    obs.note_step_time(step, dt)
                    logger.info(
                        "step %d | loss %.4f | gnorm %.3f | %s", step, loss, gnorm,
                        f"{step_tokens / dt:.0f} tok/s" if dt else "compile step",
                    )
            if self.val_dataloader is not None and self.step_scheduler.is_val_step_at(step):
                t_pause = time.perf_counter()
                with obs.track("eval", step=step):
                    self._run_validation(step)
                obs.heartbeat(step)
                window_overhead += time.perf_counter() - t_pause
            if (
                self.checkpointer.config.enabled
                and self.step_scheduler.is_ckpt_step_at(step)
                and getattr(self, "_last_saved_step", None) != step
            ):
                # the best-tracking path may have just saved this very step
                t_pause = time.perf_counter()
                with obs.track("checkpoint", step=step):
                    self._save(step)
                obs.heartbeat(step)
                window_overhead += time.perf_counter() - t_pause
            if self.chaos is not None and self.chaos.should_elastic(step):
                # topology-change injection (resilience/chaos.py): checkpoint,
                # then die carrying the resized mesh — the harness restarts the
                # recipe on it and resume takes the elastic restore path
                new_mesh = self.chaos.elastic_change(step)
                if (self.checkpointer.config.enabled
                        and getattr(self, "_last_saved_step", None) != step):
                    with obs.track("checkpoint", step=step):
                        self._save(step)
                self.checkpointer.wait()
                from automodel_tpu.resilience.elastic import ElasticTopologyChange

                raise ElasticTopologyChange(step, new_mesh)
            if self.chaos is not None and self.chaos.should_kill(step):
                # hard process death (resilience/chaos.py): SIGKILL to self,
                # no cleanup — only the supervisor can turn this into a
                # restart-from-newest-verifiable-checkpoint
                self.checkpointer.wait()
                self.chaos.kill(step)
            if self.chaos is not None and self.chaos.should_hang(step):
                # silent hang: stop heartbeating; the supervisor's staleness
                # detector must SIGABRT (capturing the watchdog stack dump)
                self.chaos.hang(step)
            with obs.track("step_end", step=step):
                obs.on_step_end(step, sync=metrics.get("loss"))
                # agreed at the CONSUMED step (deterministic across hosts even
                # while the prefetch worker advances the scheduler's own counter)
                preempted = self.step_scheduler.sigterm_agreed_at(step)
            if preempted:
                # coordinated preemption (docs/resilience.md): the flag is
                # pod-agreed, so every host reaches this save together.
                # When the remaining grace window is short, the pod agrees
                # to drop the consolidated HF export — the sharded arrays
                # + client state (all that resume needs) still land.
                logger.warning("SIGTERM received; checkpointing and exiting")
                obs.note_event(step, {"event": "preemption"})
                consolidated = None
                if (self.resilience.config.enabled
                        and self.checkpointer.config.save_consolidated
                        and self.resilience.skip_consolidated_export(
                            self.step_scheduler.sigterm_elapsed_s)):
                    consolidated = False
                with obs.track("checkpoint", step=step):
                    self._save(step, consolidated=consolidated)
                return "preempted"
        return "done"

    def _dynamics_host_step(self, obs, step: int, metrics: dict, host: "_HostMetrics",
                            stack) -> tuple[dict, str | None]:
        """Host half of the dynamics pillar for one step.

        Returns ``(dyn_row, layer_hint)``: the flat ``dynamics/*`` row when
        this step is a cadence (or excursion) sample, else ``{}``; and the
        per-layer attribution — nonfinite provenance when the guard tripped,
        otherwise the flight recorder's EMA-excursion suspect on a loss
        spike — that the resilience verdicts and skip/raise messages cite.

        The per-bucket reductions already ran in-graph; what is gated on the
        cadence here is only the device->host sync of the ~two dozen scalars
        (the overhead contract, docs/observability.md). A loss z-score
        excursion forces an off-cadence sample so the spike report and the
        attribution see the offending step itself, and dumps
        ``spike_report.json`` (never raises) outside its cooldown.
        """
        tracker = obs.dynamics
        if tracker is None or "dynamics" not in metrics:
            return {}, None
        layer_hint = None
        import math as _math

        from automodel_tpu.observability.dynamics import (
            batch_fingerprint,
            first_nonfinite_bucket,
        )

        # the recorder needs the loss each step it observes; piggyback on the
        # per-step sync resilience already pays, else observe on cadence only
        observe = self.resilience.active or tracker.due(step)
        zscore = None
        loss_h = None
        if observe:
            loss_h = float(host()["loss"])
            zscore = tracker.recorder.observe(step, loss_h)
        dyn_row: dict = {}
        if tracker.due(step) or zscore is not None:
            dyn_row = obs.dynamics_row(step, metrics["dynamics"])
        if "nonfinite_map" in metrics and bool(host().get("nonfinite", False)):
            layer_hint = first_nonfinite_bucket(metrics["nonfinite_map"])
        if zscore is not None:
            suspect = tracker.stats.suspect()
            if layer_hint is None and suspect is not None:
                layer_hint = suspect[0]
            if not tracker.recorder.in_cooldown(step):
                path = tracker.recorder.dump(
                    step, "loss_zscore", loss=loss_h,
                    zscore=None if _math.isinf(zscore) else round(zscore, 3),
                    suspect=suspect, batch=batch_fingerprint(stack),
                )
                if path is not None:
                    self.resilience.emit(step, "spike_report",
                                         path=path, layer=layer_hint)
        return dyn_row, layer_hint

    def _perform_rollback(self, bad_step: int, obs) -> bool:
        """In-process restore from the newest pod-agreed verifiable checkpoint
        (PaLM-style spike recovery: restore, then skip the offending data
        window). Returns False when no restorable checkpoint exists."""
        self.checkpointer.wait()  # commit any in-flight save before choosing
        with obs.track("rollback", step=bad_step):
            restored = self.checkpointer.load_latest_verified(
                self.train_params, self.opt_state
            )
            if restored is None:
                return False
            self.train_params, self.opt_state, client, to_step = restored
            # the live anomaly counters (rollback budget, skip streak) must
            # survive the restore — reloading them from the checkpoint would
            # reset the budget and let a persistent fault loop forever
            client.pop("resilience", None)
            self._apply_client_state(client)
            # the step counter jumps back to bad_step (monotone logs, LR
            # schedule continues) while the data cursor fast-forwards past the
            # offending window [to_step+1, bad_step] plus skip_steps fresh
            # batches — the PaLM recipe: do not re-feed the data that spiked
            skip = int(self.resilience.config.rollback.skip_steps)
            n_bad = bad_step - self.step_scheduler.step
            self.dataloader.fast_forward(
                max(n_bad + skip, 0) * self.step_scheduler.grad_acc_steps
            )
            self.step_scheduler.step = bad_step
            # fast-forward may have crossed an epoch boundary; the scheduler
            # counts epochs by completed dataloader passes, so re-sync
            self.step_scheduler.epoch = self.dataloader.epoch
            self.resilience.note_rollback(bad_step, to_step, n_bad + skip)
        return True

    def _run_validation(self, step: int):
        # validate on the SAME weights training currently sees: before a delayed
        # QAT start the train step runs un-quantized, so validation must too —
        # a quantized eval there would measure a different model than is being
        # trained and fake a train/val gap until fake_quant_after_n_steps
        qat_active = self._qat_param_fn() is not None and step >= self._qat_start_step
        eval_step = self._eval_steps.get(qat_active)
        if eval_step is None:
            from automodel_tpu.training.train_step import make_eval_step

            # training=False: no aux balance term in validation loss, pure CE
            if self.peft is not None:
                from automodel_tpu.peft.lora import merge_lora_params

                qfn = (self._qat_param_fn() or (lambda p: p)) if qat_active else (lambda p: p)
                eval_loss = lambda lora, base, b, n: self._forward_loss(
                    merge_lora_params(qfn(base), lora, self.peft), b, n, training=False
                )
                eval_step = jax.jit(make_eval_step(eval_loss, with_frozen=True))
            else:
                plain = lambda p, b, n: self._forward_loss(p, b, n, training=False)
                eval_loss = self._qat_wrap(plain) if qat_active else plain
                eval_step = jax.jit(make_eval_step(eval_loss))
            self._eval_steps[qat_active] = eval_step
        total, count = 0.0, 0
        extra = (self.params,) if self.peft is not None else ()
        for batch in self._iter_val_batches():
            n = int((batch["labels"] != -100).sum())
            total += float(eval_step(self.train_params, batch, n, *extra)) * n
            count += n
        self._log_val_loss(step, total, count)

    def _iter_val_batches(self):
        """Bounded, state-neutral pass over the validation loader.

        Restores the loader's resume cursor afterwards so every validation pass
        evaluates the SAME window: breaking out of a streaming loader at
        validation_max_batches would otherwise leave the cursor advanced, and
        each later pass would skip-drain all previously consumed examples and
        score a different (ever further) slice of the stream."""
        import itertools

        dl = self.val_dataloader
        state = dl.state_dict() if hasattr(dl, "state_dict") else None
        try:
            # islice stops BEFORE pulling batch max_val_batches+1: no wasted
            # fetch+collate (expensive for VLM patchify/mel collators)
            yield from itertools.islice(dl, self.max_val_batches)
        finally:
            if state is not None and hasattr(dl, "load_state_dict"):
                dl.load_state_dict(state)

    def _log_val_loss(self, step: int, total: float, count: float,
                      extra_sums: dict[str, float] | None = None):
        """Token-weighted mean aggregated across the pod: each process sees a
        different dataloader shard, so a host-local mean would log a different
        val_loss per host (reference allreduces val loss the same way,
        train_ft.py:1456). ``extra_sums``: additional per-example metric SUMS
        sharing ``count`` as denominator (biencoder acc@1/recall@k/MRR) —
        summed across hosts like the loss."""
        extra_sums = extra_sums or {}
        if jax.process_count() > 1:
            import numpy as np
            from jax.experimental import multihost_utils

            # ship each host sum as an f32 hi/lo (Dekker) pair and rebuild in
            # np.float64 on the host: jnp.float64 silently downcasts to f32
            # without jax_enable_x64, which loses the low-order bits of large
            # token-weighted loss sums exactly when the pod is big enough for
            # them to matter
            vals = np.asarray([total, float(count), *extra_sums.values()],
                              np.float64)
            hi = vals.astype(np.float32)
            lo = (vals - hi.astype(np.float64)).astype(np.float32)
            agg = np.asarray(multihost_utils.process_allgather(
                jnp.asarray(np.stack([hi, lo]), jnp.float32)))
            # agg: [hosts, 2, K] -> exact per-host f64 values, summed in f64
            sums = (agg[:, 0, :].astype(np.float64)
                    + agg[:, 1, :].astype(np.float64)).sum(axis=0)
            total, count = float(sums[0]), float(sums[1])
            extra_sums = {k: float(sums[2 + i])
                          for i, k in enumerate(extra_sums)}
        if count:
            val_loss = total / count
            extras = {k: v / count for k, v in extra_sums.items()}
            self.val_metric_logger.log(step, val_loss=val_loss, **extras)
            for lg in self.experiment_loggers:
                lg.log(step, val_loss=val_loss, **extras)
            logger.info("validation @ step %d: loss %.4f%s", step, val_loss,
                        "".join(f" | {k} {v:.4f}" for k, v in extras.items()))
            # best-checkpoint tracking (reference base_recipe.py:383-425): save
            # the improving step and point the `best` symlink at it. is_best()
            # decides on process 0 and broadcasts internally — per-host
            # filesystem reads can skew, and orbax save is a collective, so a
            # split decision would deadlock the pod.
            if self.checkpointer.config.enabled and bool(self.cfg.get("checkpoint.save_best", True)):
                if self.checkpointer.is_best(val_loss):
                    self._save(step)
                    self.checkpointer.mark_best(step, val_loss)

    def _save(self, step: int, consolidated: bool | None = None):
        """PEFT saves are adapter-only (reference PEFT checkpoint addon,
        checkpoint/addons.py); consolidated HF export merges the adapter so the
        output is a plain HF model either way. ``consolidated=False`` drops the
        HF export for this save (preemption under a short grace window)."""
        self._last_saved_step = step
        client = {
            "rng": self.rng,
            "step_scheduler": self.step_scheduler,
            "dataloader": self.dataloader,
            "resilience": self.resilience,
        }
        if self._pipeline is not None:
            # prefetch: the live scheduler/dataloader have been advanced past
            # the consumed step by the worker — checkpoint the consumed-position
            # snapshots instead, so resume replays every in-flight batch
            client.update(self._pipeline.client_states())
        do_consolidated = (self.checkpointer.config.save_consolidated
                           if consolidated is None else consolidated)
        hf_params = None
        if self.peft is not None:
            client["peft_config"] = self.peft.to_dict()
            if do_consolidated:
                hf_params = self._merge_lora(self.params, self.train_params)
        d = self.checkpointer.save(
            step, self.train_params, self.opt_state, client_states=client,
            hf_params=hf_params, consolidated=consolidated,
        )
        self.resilience.record_checkpoint(step)
        if d and self.chaos is not None and self.chaos.should_kill(step, point="save"):
            # torn-write injection: with async save the arrays are still
            # in flight and the manifest/latest commit has NOT happened — the
            # restart must reject this step and walk back (checkpointing.py)
            self.chaos.kill(step)
        if d and self.chaos is not None and self.chaos.should_corrupt(step):
            # fault injection: finalize first (manifest written, latest committed)
            # so the truncation exercises verify-and-walk-back, not a half save
            self.checkpointer.wait()
            self.chaos.corrupt_checkpoint(step, d)
        if d and self.peft is not None and do_consolidated:
            # adapter-only HF PEFT export alongside the merged model: deployable
            # via peft.PeftModel without shipping base weights
            from automodel_tpu.checkpoint.checkpointing import _full_host_array
            from automodel_tpu.checkpoint.peft_export import save_peft_adapter

            save_peft_adapter(
                os.path.join(d, "hf_adapter"), self.train_params, self.peft,
                self.model.state_dict_adapter().entries,
                host_fn=_full_host_array,
                base_model_name=self.cfg.get("model.pretrained_model_name_or_path"),
                write=jax.process_index() == 0,
            )


def main(cfg: ConfigNode | None = None, argv=None):
    if cfg is None:
        cfg = parse_args_and_load_config(argv)
    recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg)
    recipe.setup()
    recipe.run_train_validation_loop()
    return recipe


if __name__ == "__main__":
    main()
