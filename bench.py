"""Single-chip SFT throughput benchmark (driver-run; prints ONE JSON line).

Benchmarks the BASELINE.json config #1 shape — Llama-3.2-1B-class SFT, mock data,
bf16 — on whatever single accelerator is attached, and reports tokens/sec/chip at
seq 2048 (primary, continuity with earlier rounds) AND seq 4096 (the reference's
own measurement condition, BASELINE.md) in extra.

``vs_baseline`` is hardware-normalized: the reference's headline single-GPU row is
Llama3-8B LoRA on H100 at 402 TFLOPs/s/GPU = 40.6% MFU against 989 bf16 peak
(BASELINE.md / docs/performance-summary.md). We report our model-FLOPs MFU against
the attached chip's bf16 peak and define vs_baseline = our_MFU / 0.406 — comparing
compiler+framework efficiency rather than raw chips (an H100 has ~5x the FLOPs of
the v5e this runs on).

Failure contract: the LAST stdout line is ALWAYS machine-parseable JSON — the
``__main__`` guard catches BaseException and flushes stderr before the final
print, so no traceback can displace or interleave with it. A measurement needs
the chip: when no accelerator is attached, or the backend cannot initialize, or
it dies at the first dispatch (a trivial jitted canary probes this), the last
line is ``{"ok": false, "error": ...}`` and the exit code is non-zero. There is
no CPU stand-in. ``--cpu`` (with ``--matrix`` or ``--tune``) is an explicit
request for a toy-size rehearsal of the harness: its rows say
``"platform": "cpu"`` and carry their rate as ``cpu_tokens_per_sec_per_device``,
never under a device metric's name. ``--matrix`` runs every cell in a child
process, so its parent never touches a JAX backend: a chip belongs to one
process at a time. ``extra.input_pipeline`` reports seconds/step for the same
loop with the overlapped input pipeline off vs on.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def device_peak_tflops(device: str) -> float:
    """bf16 peak for MFU math (shared by bench.py and the tools/ bench
    scripts); an unknown device is an error. Delegates to the observability
    spec table — one source of truth with the roofline math."""
    from automodel_tpu.observability.hlo_costs import device_peak_tflops as _peak

    return _peak(device)


def llama_flops_per_token(cfg, seq_len: int) -> float:
    """Training FLOPs/token (fwd+bwd = 3x fwd) incl. attention quadratic term."""
    d, i, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    n, k, h, v = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.vocab_size
    qkv = 2 * d * (n + 2 * k) * h
    o = 2 * n * h * d
    attn_scores = 2 * 2 * seq_len * n * h  # qk^T + av per token
    mlp = 3 * 2 * d * i
    per_layer = qkv + o + attn_scores + mlp
    embed_head = 2 * d * v
    return 3.0 * (L * per_layer + embed_head)


def _measure(cfg, seq_len: int, micro_batch: int, n_steps: int, backend=None,
             dynamics: bool = False):
    import jax
    import jax.numpy as jnp
    import optax

    from automodel_tpu.models.common.backend import BackendConfig
    from automodel_tpu.models.llama.model import LlamaForCausalLM
    from automodel_tpu.ops.losses import masked_cross_entropy
    from automodel_tpu.training.train_step import make_train_step

    # measured on-chip (single v5-class): pallas flash (1024, 1024) blocks +
    # remat "mlp_attn_dots" (save gate/up/k/v/attn-out; backward replays only the
    # q projection + elementwise) + momentum-free factored-rms (pure Adafactor,
    # the T5/PaLM optimizer — its ~zero state is what affords that remat policy
    # on a 16GB chip) + attention_segments=False (mock SFT batches are unpacked
    # and full-length: causal masking already isolates pads, so the kernels skip
    # the segment loads/selects — clean-run-to-clean-run +4.1% at 2048, +5.5%
    # at 4096) = 13.68k tok/s / 57.1% MFU at 2048, 11.89k / 54.5% at 4096
    # (stable over repeats). The ladder: fp32-nu adamw -> remat "none" 11.7k;
    # bf16-nu -> "mlp_gate_dot" 12.0k; factored+bf16 trace -> "mlp_dots"
    # 12.87k; momentum-free -> "mlp_attn_dots" 13.14k; segment-free attention
    # -> 13.68k; round-5 fused dq+dkv backward (one s/p recompute feeding all
    # three grads, 5 bwd block-matmuls instead of 7) -> 14.38k @2048 / 12.78k
    # @4096 (60.0% / 58.5% MFU). Fused q-block sweep: 512 best (256: -2%,
    # 1024: scoped-VMEM OOM at 19.6M/16M). Round-4 dead ends at 4096
    # (tools/bench_seq4096_sweep.py): saving q too in remat (-1.3pt, bandwidth),
    # dkv q-block 256 (-2.1pt) or 1024 (+-0), fwd blocks (2048,1024) and
    # micro_batch 3/4 (OOM even with linear-CE — the mlp saved tensors dominate).
    if backend is None:
        backend = BackendConfig(dtype="bfloat16", remat_policy="mlp_attn_dots",
                                attention="flash", attention_segments=False)
    model = LlamaForCausalLM(cfg, backend)

    params = model.init(jax.random.key(0), jnp.dtype(backend.dtype))
    optimizer = optax.chain(
        optax.scale_by_factored_rms(),
        optax.scale(-1e-5),
    )
    opt_state = jax.jit(optimizer.init)(params)

    def forward_loss(p, batch, num_label_tokens):
        logits = model(p, batch["input_ids"], positions=batch["positions"],
                       segment_ids=batch["segment_ids"])
        return masked_cross_entropy(logits, batch["labels"], num_label_tokens)

    # --dynamics: the per-subtree telemetry reductions ride in-graph (the
    # overhead the gate tolerance must absorb, docs/observability.md)
    step = jax.jit(make_train_step(forward_loss, optimizer, dynamics=dynamics),
                   donate_argnums=(0, 1))

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (1, micro_batch, seq_len)).astype(np.int32)
    batch = {
        "input_ids": jnp.asarray(ids),
        "labels": jnp.asarray(ids),
        "positions": jnp.broadcast_to(jnp.arange(seq_len, dtype=jnp.int32), ids.shape),
        "segment_ids": jnp.ones_like(jnp.asarray(ids)),
    }

    # warmup/compile
    params, opt_state, m = step(params, opt_state, batch)
    jax.block_until_ready(m["loss"])

    t0 = time.perf_counter()
    for _ in range(n_steps):
        params, opt_state, m = step(params, opt_state, batch)
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0
    return n_steps * micro_batch * seq_len / dt


def _prefetch_probe(n_steps: int = 8, item_delay_s: float = 0.004) -> dict:
    """Input-pipeline overlap measurement: seconds/step for an identical tiny
    loop with the loader synchronous vs overlapped (host prefetch thread +
    device double-buffering). ``item_delay_s`` stands in for real host-side
    tokenize/pack cost; the overlapped path hides it behind device compute."""
    import jax
    import jax.numpy as jnp

    from automodel_tpu.data.collate import stack_batches
    from automodel_tpu.data.llm.mock import MockSFTDataset
    from automodel_tpu.data.loader import DataLoader
    from automodel_tpu.data.prefetch import InputPipeline, PrefetchConfig
    from automodel_tpu.training.step_scheduler import StepScheduler

    def collate(samples):
        return {"x": np.asarray([s["input_ids"] for s in samples], np.int32)}

    def f_impl(x):
        # device work of the same magnitude as the host-side cost — overlap is
        # only visible when there is compute to hide the input latency behind
        v = x.reshape(-1).astype(jnp.float32)[:512]
        a = jnp.outer(v, v) / 512.0
        for _ in range(12):
            a = jnp.tanh(a @ a)
        return jnp.sum(a)

    f = jax.jit(f_impl)

    def run(enabled: bool) -> float:
        ds = MockSFTDataset(vocab_size=512, seq_len=128,
                            num_samples=8 * (n_steps + 2), seed=0,
                            item_delay_s=item_delay_s)
        dl = DataLoader(ds, batch_size=8, collate_fn=collate, seed=0)
        sched = StepScheduler(grad_acc_steps=1, num_epochs=1,
                              max_steps=n_steps + 1, dataloader=dl,
                              handle_sigterm=False)
        pipe = InputPipeline(scheduler=sched, dataloader=dl,
                             stack_fn=stack_batches, put_fn=jax.device_put,
                             config=PrefetchConfig(enabled=enabled))
        try:
            # first step covers compile + queue spin-up; timed steps follow
            first = pipe.get()
            f(first.stack["x"]).block_until_ready()
            done = 0
            t0 = time.perf_counter()
            while done < n_steps:
                item = pipe.get()
                if item is None:
                    break
                f(item.stack["x"]).block_until_ready()
                done += 1
            dt = time.perf_counter() - t0
        finally:
            pipe.close()
        return dt / max(done, 1)

    sync = run(False)
    overlapped = run(True)
    return {
        "sync_s_per_step": round(sync, 5),
        "prefetch_s_per_step": round(overlapped, 5),
        "overlap_speedup": round(sync / overlapped, 3) if overlapped > 0 else None,
    }


def _attach_prefetch_probe(doc: dict) -> dict:
    """Best-effort: the overlap numbers ride along, they never fail the bench."""
    try:
        doc["extra"]["input_pipeline"] = _prefetch_probe()
    except Exception as exc:  # noqa: BLE001
        doc["extra"]["input_pipeline"] = {"error": repr(exc)}
    return doc


def _full_bench(dynamics: bool = False) -> dict:
    import jax

    from automodel_tpu.models.llama.model import LlamaConfig

    # Llama-3.2-1B dims
    cfg = LlamaConfig(
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=8192,
        num_hidden_layers=16,
        num_attention_heads=32,
        num_key_value_heads=8,
        head_dim=64,
        rope_theta=500000.0,
        tie_word_embeddings=True,
        max_position_embeddings=131072,
    )
    tps = _measure(cfg, seq_len=2048, micro_batch=4, n_steps=20, dynamics=dynamics)
    tps_4k = _measure(cfg, seq_len=4096, micro_batch=2, n_steps=10, dynamics=dynamics)

    device = str(jax.devices()[0])
    peak = device_peak_tflops(jax.devices()[0].device_kind)

    f_2k = llama_flops_per_token(cfg, 2048)
    f_4k = llama_flops_per_token(cfg, 4096)
    # reference 8B dims for the FLOPs-equivalent conversion
    cfg8b = LlamaConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
    )
    f_8b = llama_flops_per_token(cfg8b, 4096)
    mfu = tps * f_2k / 1e12 / peak
    mfu_4k = tps_4k * f_4k / 1e12 / peak
    ref_mfu = 402.0 / 989.0  # reference Llama3-8B LoRA on H100, seq 4096

    return _attach_prefetch_probe({
        "ok": True,
        "metric": "llama3.2-1b SFT tokens/sec/chip (bf16, seq 2048)",
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / ref_mfu, 4),
        "extra": {
            "model_tflops_per_sec": round(tps * f_2k / 1e12, 1),
            "mfu": round(mfu, 4),
            "seq4096_tokens_per_sec": round(tps_4k, 1),
            "seq4096_mfu": round(mfu_4k, 4),
            "seq4096_vs_baseline": round(mfu_4k / ref_mfu, 4),
            "assumed_peak_tflops": peak,
            "8b_equiv_tokens_per_sec": round(tps_4k * f_4k / f_8b, 1),
            "device": device,
            "dynamics": dynamics,
        },
    })


# ---------------------------------------------------------------- matrix mode
MATRIX_SEQ_LENS = (2048, 4096, 8192)


def _matrix_dense_model(cpu: bool):
    from automodel_tpu.models.common.backend import BackendConfig
    from automodel_tpu.models.llama.model import LlamaForCausalLM

    cfg = _tune_model_config(cpu)
    if cpu:
        backend = BackendConfig(dtype="float32")
    else:
        # the tuned single-chip backend (see _measure)
        backend = BackendConfig(dtype="bfloat16", remat_policy="mlp_attn_dots",
                                attention="flash", attention_segments=False)
    return LlamaForCausalLM(cfg, backend), cfg.vocab_size


def _matrix_moe_model(cpu: bool, dispatcher: str = "dense",
                      experts_backend: str = "ragged_dot", a2a_chunks: int = 1):
    from automodel_tpu.models.common.backend import BackendConfig
    from automodel_tpu.models.qwen3_moe.model import Qwen3MoeForCausalLM

    moe_knobs = dict(dispatcher=dispatcher, experts_backend=experts_backend,
                     a2a_chunks=a2a_chunks)
    if cpu:
        hf = dict(
            vocab_size=2048, hidden_size=256, intermediate_size=512,
            moe_intermediate_size=128, num_hidden_layers=4,
            num_attention_heads=8, num_key_value_heads=4, head_dim=32,
            max_position_embeddings=512, num_experts=8, num_experts_per_tok=2,
            norm_topk_prob=True, router_aux_loss_coef=0.01,
        )
        backend = BackendConfig(dtype="float32", **moe_knobs)
    else:
        # 1B-class MoE: same token FLOPs ballpark as the dense row so the
        # dense-vs-moe tokens/s gap in one matrix is the dispatch overhead
        hf = dict(
            vocab_size=128256, hidden_size=2048, intermediate_size=4096,
            moe_intermediate_size=1024, num_hidden_layers=16,
            num_attention_heads=32, num_key_value_heads=8, head_dim=64,
            max_position_embeddings=131072, num_experts=16,
            num_experts_per_tok=2, norm_topk_prob=True,
            router_aux_loss_coef=0.01,
        )
        backend = BackendConfig(dtype="bfloat16", remat_policy="mlp_attn_dots",
                                attention="flash", attention_segments=False,
                                **moe_knobs)
    return Qwen3MoeForCausalLM.from_config(hf, backend), hf["vocab_size"]


# the moe_a2a cells exercise the explicit EP dispatch hot path: dispatcher=a2a
# over an ep mesh spanning every device, chunked so expert GEMMs overlap the
# next chunk's all_to_all, with both grouped-GEMM backends. One seq point is
# enough — the dispatch/overlap story does not need the seq sweep.
MATRIX_A2A_KINDS = ("moe_a2a", "moe_a2a_pallas")


def _rate_key(cpu: bool) -> str:
    """Row key of the token rate. A --cpu rehearsal never writes its number
    under the device metric's name (the perf gate reads either)."""
    return "cpu_tokens_per_sec_per_device" if cpu else "tokens_per_sec_per_chip"


def _matrix_cells() -> list[tuple[str, int]]:
    """Every (kind, nominal_seq) cell in the matrix: dense/moe across
    MATRIX_SEQ_LENS plus the a2a hot-path variants at the headline seq."""
    cells = [(kind, nominal) for kind in ("dense", "moe")
             for nominal in MATRIX_SEQ_LENS]
    cells += [(kind, MATRIX_SEQ_LENS[0]) for kind in MATRIX_A2A_KINDS]
    return cells


def _matrix_cell(kind: str, nominal_seq: int, cpu: bool,
                 dynamics: bool = False,
                 profile: bool = False) -> tuple[list[dict], dict | None]:
    """One {model} x {seq} cell: AOT-compile once, run prefetch off then on.

    Returns ``(rows, signals_cell)``. CPU rows keep the nominal seq as the row
    label (so baselines line up across hosts) and record the actually
    measured ``measured_seq_len``; MoE rows add routed tokens/s/chip and the
    a2a share of collective bytes from the compiled HLO. With ``profile``,
    one extra step runs under a ``jax.profiler`` trace after the timed loops
    and the measured category breakdown (``measured_*`` + ``overlap_frac``,
    observability/trace_analysis.py) lands on the prefetch-on row — the
    production config — plus a schema-shaped signals cell (signals.py) for
    the summary doc; without it ``signals_cell`` is None.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from automodel_tpu.data.collate import stack_batches
    from automodel_tpu.data.llm.mock import MockSFTDataset
    from automodel_tpu.data.loader import DataLoader
    from automodel_tpu.data.prefetch import InputPipeline, PrefetchConfig
    from automodel_tpu.observability.hlo_costs import (
        collective_bytes,
        collective_bytes_by_axis,
    )
    from automodel_tpu.observability.memory import device_memory_stats
    from automodel_tpu.observability.memory_plan import compiled_memory_attribution
    from automodel_tpu.ops.losses import masked_cross_entropy
    from automodel_tpu.training.step_scheduler import StepScheduler
    from automodel_tpu.training.train_step import make_train_step

    a2a = kind in MATRIX_A2A_KINDS
    is_moe = kind == "moe" or a2a
    rules = None
    if a2a:
        from automodel_tpu.parallel.mesh import MeshContext, default_sharding_rules

        # an ep mesh over every device: the explicit dispatch path degrades
        # gracefully at ep=1 (single-host runs without forced devices), and
        # a2a cells always carry overlap_frac — the a2a/compute overlap IS
        # the metric these cells exist to gate, so the one profiled step is
        # not optional here
        mesh = MeshContext(ep=jax.device_count()).build_mesh()
        rules = default_sharding_rules().with_mesh(mesh)
        model, vocab = _matrix_moe_model(
            cpu, dispatcher="a2a", a2a_chunks=2,
            experts_backend="pallas" if kind == "moe_a2a_pallas"
            else "ragged_dot")
        profile = True
    else:
        model, vocab = (_matrix_moe_model(cpu) if is_moe
                        else _matrix_dense_model(cpu))
    seq_len = min(nominal_seq, 128) if cpu else nominal_seq
    micro_batch = 2 if cpu else {2048: 4, 4096: 2, 8192: 1}[nominal_seq]
    n_steps = 3 if cpu else 10
    devices = jax.device_count()
    if a2a:
        # the dispatch shard_map splits the batch dim over ep: round the
        # microbatch up to a whole multiple of the mesh
        micro_batch = -(-micro_batch // devices) * devices

    def forward_loss(p, batch, num_label_tokens):
        if is_moe:
            out, stats = model(
                p, batch["input_ids"], positions=batch["positions"],
                segment_ids=batch["segment_ids"],
                token_mask=batch["segment_ids"] != 0, training=True,
                rules=rules,
            )
            loss = masked_cross_entropy(out, batch["labels"], num_label_tokens)
            aux = {"expert_load": stats["expert_load"]}
            if a2a:
                aux["dropped_frac"] = stats["dropped_token_frac"]
            return loss, aux
        logits = model(p, batch["input_ids"], positions=batch["positions"],
                       segment_ids=batch["segment_ids"])
        return masked_cross_entropy(logits, batch["labels"], num_label_tokens)

    optimizer = optax.chain(optax.scale_by_factored_rms(), optax.scale(-1e-5))
    step_fn = make_train_step(forward_loss, optimizer, dynamics=dynamics)

    if a2a:
        # sharded init: expert weights land distributed over the ep axis, so
        # the lowered step is the real multi-device dispatch program
        shardings = rules.tree_sharding(model.logical_axes())
        from automodel_tpu.parallel.sharding_utils import make_sharded_init

        params = jax.jit(
            lambda k: model.init(k, jnp.dtype(model.backend.dtype)),
            out_shardings=shardings)(jax.random.key(0))
        opt_state = make_sharded_init(optimizer, params, mesh)(params)
        # pin the carry outputs to the carry input shardings — XLA is
        # otherwise free to re-lay the donated params between steps, which
        # the AOT-compiled call rejects on the next invocation
        step = jax.jit(
            step_fn, donate_argnums=(0, 1),
            out_shardings=(jax.tree.map(lambda a: a.sharding, params),
                           jax.tree.map(lambda a: a.sharding, opt_state),
                           None))
    else:
        step = jax.jit(step_fn, donate_argnums=(0, 1))
        params = model.init(jax.random.key(0), jnp.dtype(model.backend.dtype))
        opt_state = jax.jit(optimizer.init)(params)

    # AOT compile from a synthetic stack of the pipeline's exact shapes; the
    # optimized HLO also yields the a2a byte share
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (1, micro_batch, seq_len)).astype(np.int32)
    sample_stack = {
        "input_ids": ids, "labels": ids.copy(),
        "positions": np.ascontiguousarray(np.broadcast_to(
            np.arange(seq_len, dtype=np.int32), ids.shape)),
        "segment_ids": np.ones_like(ids),
    }
    compiled = step.lower(params, opt_state, sample_stack).compile()
    a2a_share = 0.0
    hlo = None
    try:
        hlo = compiled.as_text()
        total = sum(collective_bytes(hlo).values())
        moe_a2a = collective_bytes_by_axis(hlo).get("moe_a2a", 0)
        a2a_share = round(moe_a2a / total, 4) if total else 0.0
    except Exception:  # noqa: BLE001 — a2a share is best-effort decoration
        pass
    # memory-analysis peak: XLA's own args+out+temp-alias attribution of the
    # compiled step — available on every backend, deterministic for a given
    # (model, seq, batch), and the CPU fallback for the hbm_gib_peak gate key
    # where no allocator counters exist
    attribution = compiled_memory_attribution(compiled)
    compiled_peak_gib = (round(attribution["peak_est"] / 2**30, 4)
                         if attribution else None)

    def collate(samples):
        # MockSFTDataset emits seq_len + 1 ids (next-token shift headroom);
        # trim to the AOT-compiled width so shapes match the lowered step
        arr = np.asarray([s["input_ids"] for s in samples], np.int32)[:, :seq_len]
        return {
            "input_ids": arr, "labels": arr.copy(),
            "positions": np.ascontiguousarray(np.broadcast_to(
                np.arange(arr.shape[-1], dtype=np.int32), arr.shape)),
            "segment_ids": np.ones_like(arr),
        }

    def make_pipeline(prefetch: bool) -> InputPipeline:
        ds = MockSFTDataset(vocab_size=vocab, seq_len=seq_len,
                            num_samples=micro_batch * (n_steps + 3), seed=0,
                            item_delay_s=0.002)
        dl = DataLoader(ds, batch_size=micro_batch, collate_fn=collate, seed=0)
        sched = StepScheduler(grad_acc_steps=1, num_epochs=1,
                              max_steps=n_steps + 1, dataloader=dl,
                              handle_sigterm=False)
        return InputPipeline(scheduler=sched, dataloader=dl,
                             stack_fn=stack_batches, put_fn=jax.device_put,
                             config=PrefetchConfig(enabled=prefetch))

    rows = []
    for prefetch in (False, True):
        pipe = make_pipeline(prefetch)
        try:
            first = pipe.get()
            params, opt_state, m = compiled(params, opt_state, first.stack)
            jax.block_until_ready(m["loss"])  # flush warmup before the clock starts
            done = 0
            t0 = time.perf_counter()
            while done < n_steps:
                item = pipe.get()
                if item is None:
                    break
                params, opt_state, m = compiled(params, opt_state, item.stack)
                done += 1
            jax.block_until_ready(m["loss"])  # closes the timed window
            dt = time.perf_counter() - t0
        finally:
            pipe.close()
        row = {
            "matrix_row": True, "model": kind, "seq_len": nominal_seq,
            "prefetch": prefetch, "steps": max(done, 1),
            "platform": jax.devices()[0].platform,
            _rate_key(cpu): round(done * micro_batch * seq_len / dt / devices, 1),
        }
        if dynamics:
            # condition marker: a dynamics-on row must not be compared against
            # a dynamics-off baseline without knowing it
            row["dynamics"] = True
        # gate key: measured allocator high-water where the platform has one
        # (TPU), else the compiled-step estimate — the source rides along so
        # a baseline from one never silently gates a run from the other
        mem_stats = device_memory_stats()
        if mem_stats.get("hbm_gib_peak") is not None:
            row["hbm_gib_peak"] = mem_stats["hbm_gib_peak"]
            row["hbm_source"] = "device"
        elif compiled_peak_gib is not None:
            row["hbm_gib_peak"] = compiled_peak_gib
            row["hbm_source"] = "compiled"
        if cpu:
            row["measured_seq_len"] = seq_len
            row["micro_batch"] = micro_batch
        if is_moe:
            # routed token copies through the expert GEMMs — the volume a
            # grouped-GEMM / fused-dispatch optimization has to move
            routed_per_step = float(np.asarray(m["expert_load"]).sum())
            row["moe/" + _rate_key(cpu)] = round(
                routed_per_step * done / dt / devices, 1)
            row["a2a_byte_share"] = a2a_share
            if a2a:
                row["dropped_token_frac"] = round(float(m["dropped_frac"]), 4)
        rows.append(row)
    signals_cell = None
    if profile:
        # one profiled step AFTER the timed loops: params/opt_state are warm
        # and nothing downstream needs them (donation deletes the inputs)
        measured, signals_cell = _profile_cell_step(
            compiled, params, opt_state, sample_stack, hlo,
            cell={"model": kind, "seq_len": nominal_seq})
        rows[-1].update(measured)  # the prefetch-on (production) row
    return rows, signals_cell


def _profile_cell_step(compiled, params, opt_state, sample_stack, hlo,
                       cell) -> tuple[dict, dict | None]:
    """One step under a jax.profiler trace -> measured row keys + signals cell.

    Best-effort decoration like the a2a share: any failure returns empty and
    the bench rows stand on their timed numbers alone.
    """
    import shutil
    import tempfile

    import jax

    from automodel_tpu.observability import signals as sig
    from automodel_tpu.observability import trace_analysis as ta
    from automodel_tpu.observability.hlo_costs import (
        compiled_cost_metrics,
        device_specs,
        roofline_metrics,
    )

    td = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        try:
            batch = jax.device_put(sample_stack)
            jax.profiler.start_trace(td)
            try:
                _p, _o, m = compiled(params, opt_state, batch)
                jax.block_until_ready(m["loss"])  # the trace must hold the whole step
            finally:
                jax.profiler.stop_trace()
            report = ta.analyze_trace(td, hlo_text=hlo, steps_hint=1)
        finally:
            shutil.rmtree(td, ignore_errors=True)
        if report is None:
            return {}, None
        costs = compiled_cost_metrics(compiled, hlo_text=hlo)
        spec = device_specs(jax.devices()[0].device_kind)
        roof = roofline_metrics(costs, spec) if spec is not None else {}
        summary = report.summary_row()
        summary.update(ta.reconcile_with_roofline(report, roof))
        measured = {k: summary[k] for k in
                    ("measured_step_time_s", "measured_t_compute_s",
                     "measured_t_comm_s", "measured_t_moe_a2a_s",
                     "measured_t_host_s", "measured_frac_compute",
                     "measured_frac_comm", "measured_frac_moe_a2a",
                     "measured_frac_host", "overlap_frac", "measured_bound")
                    if k in summary}
        signals_cell = sig.build_cell(cell=cell, roofline=roof or None,
                                      costs=costs, trace_summary=summary)
        return measured, signals_cell
    except Exception as exc:  # noqa: BLE001 — profiling must not kill the bench
        print(f"bench: profiled step failed ({exc!r}); rows carry no "
              "measured_* keys", file=sys.stderr)
        return {}, None


def _matrix_bench_inline(cpu: bool, dynamics: bool = False,
                         profile: bool = False) -> dict:
    """``--no-isolate``: every cell in THIS process (the pre-r05 monolith —
    one dead cell still kills the rest). Kept for debugging a single
    interpreter; the default path is the per-cell subprocess harness below."""
    import jax

    rows: list[dict] = []
    signal_cells: list[dict] = []
    for kind, nominal in _matrix_cells():
        cell_rows, signals_cell = _matrix_cell(
            kind, nominal, cpu, dynamics=dynamics, profile=profile)
        for row in cell_rows:
            print(json.dumps(row), flush=True)
            rows.append(row)
        if signals_cell is not None:
            signal_cells.append(signals_cell)
    headline = next(
        (r[_rate_key(cpu)] for r in rows
         if r["model"] == "dense" and r["seq_len"] == 2048 and r["prefetch"]),
        None,
    )
    doc = {
        "ok": True,
        "metric": "bench matrix: {dense,moe} x seq x prefetch tokens/s/chip",
        "value": headline,
        "unit": "tokens/s/chip",
        "vs_baseline": None,
        "matrix": rows,
        "extra": {"device": str(jax.devices()[0]), "rows": len(rows)},
    }
    if signal_cells:
        from automodel_tpu.observability.signals import build_signals

        doc["signals"] = build_signals(signal_cells)
    if cpu:
        doc["extra"]["platform"] = "cpu"
        doc["unit"] = "tokens/s/cpu-device (toy rehearsal, not a device rate)"
    return doc


def _cell_argv(spec: dict, script: str | None = None) -> list[str]:
    """The child invocation for one cell: same interpreter, same script,
    ``--cell kind:seq`` plus the run's mode flags."""
    import os

    argv = [sys.executable, script or os.path.abspath(__file__),
            "--cell", f"{spec['kind']}:{spec['seq_len']}"]
    for flag in ("cpu", "dynamics", "profile"):
        if spec.get(flag):
            argv.append(f"--{flag}")
    return argv


def _bench_chaos_hook(cell_id: str) -> None:
    """CI fault injection for the harness itself: ``AUTOMODEL_BENCH_CHAOS``
    (JSON: ``{"fail": [cell ids], "hang": [cell ids], "hang_s": n}``) forces
    a named cell to die or to wedge past its timeout — proving a poisoned
    cell costs one cell, never the artifact. Resume without the env var
    re-runs only the poisoned cells."""
    import os

    raw = os.environ.get("AUTOMODEL_BENCH_CHAOS")
    if not raw:
        return
    spec = json.loads(raw)
    if cell_id in (spec.get("fail") or ()):
        raise RuntimeError(f"bench chaos: forced failure in cell {cell_id}")
    if cell_id in (spec.get("hang") or ()):
        hold = float(spec.get("hang_s", 3600.0))
        print(f"bench chaos: hanging cell {cell_id} for {hold:.0f}s",
              file=sys.stderr)
        time.sleep(hold)


def _cell_main(cell: str, cpu: bool, dynamics: bool = False,
               profile: bool = False) -> dict:
    """``--cell kind:seq`` child mode: one isolated cell, rows as JSON lines,
    then a final doc the harness records (``{"ok", "cell", "rows", "signals"}``
    — the rows ride the doc so the ledger can replay them on resume)."""
    kind, _, seq = cell.partition(":")
    cell_id = f"{kind}_s{seq}"
    _bench_chaos_hook(cell_id)
    rows, signals_cell = _matrix_cell(kind, int(seq), cpu,
                                      dynamics=dynamics, profile=profile)
    for row in rows:
        print(json.dumps(row), flush=True)
    return {"ok": True, "cell": cell_id, "rows": rows, "signals": signals_cell}


def _matrix_bench(cpu: bool, dynamics: bool = False, profile: bool = False,
                  out_dir: str = "bench_matrix", resume: bool = False,
                  cell_timeout_s: float = 900.0, cell_retries: int = 1) -> dict:
    """{dense, moe} x seq {2048,4096,8192} plus the moe_a2a hot-path cells
    at the headline seq (_matrix_cells), each cell in an isolated
    subprocess with a wall budget (resilience/harness.py). One JSON line per
    row as it lands; completed cells recorded in the resumable
    ``<out_dir>/matrix_ledger.json``; a failed cell becomes a taxonomy-labeled
    ledger entry instead of killing the matrix (BENCH_r05). The summary doc
    keeps the gate contract (``matrix`` rows + headline) and adds per-cell
    status (``cells``) plus the preflight verdict; ``ok`` is False when any
    cell did not run. ``--resume`` re-runs only the incomplete cells,
    byte-identically preserving completed entries."""
    import os

    from automodel_tpu.resilience.harness import (
        CellLedger, run_cells, run_isolated,
    )

    os.makedirs(out_dir, exist_ok=True)
    ledger_path = os.path.join(out_dir, "matrix_ledger.json")
    if not resume and os.path.exists(ledger_path):
        # a fresh run must not silently inherit a stale ledger's completions
        os.unlink(ledger_path)
    ledger = CellLedger(ledger_path)

    # preflight health rung in its own subprocess: a wedged backend poisons
    # one probe, and the verdict is stamped into the artifact header
    script = os.path.abspath(__file__)
    pf_argv = [sys.executable, script, "--preflight"] + (["--cpu"] if cpu else [])
    pf = run_isolated(pf_argv, timeout_s=min(cell_timeout_s, 300.0))
    pf_doc = next((d for d in reversed(pf["docs"]) if "ok" in d), None) or {
        "ok": False,
        "error": ("preflight timed out" if pf["timed_out"]
                  else f"preflight rc={pf['returncode']} with no JSON line"),
        "tail": pf["stderr_tail"][-2000:],
    }
    ledger.set_header({"preflight": pf_doc, "mode": {
        "cpu": cpu, "dynamics": dynamics, "profile": profile}})
    if not pf_doc.get("ok"):
        return {
            "ok": False,
            "metric": "bench matrix: {dense,moe} x seq x prefetch tokens/s/chip",
            "value": None, "unit": "tokens/s/chip", "vs_baseline": None,
            "error": f"preflight failed: {pf_doc.get('error')}",
            "matrix": [], "cells": [],
            "extra": {"preflight": pf_doc, "ledger": ledger_path},
        }

    specs = [
        {"id": f"{kind}_s{nominal}", "kind": kind, "seq_len": nominal,
         "cpu": cpu, "dynamics": dynamics, "profile": profile}
        for kind, nominal in _matrix_cells()
    ]

    def emit(entry: dict, replayed: bool) -> None:
        outcome = entry["outcome"]
        if outcome["status"] == "ran":
            for row in outcome.get("rows") or []:
                print(json.dumps(row), flush=True)
        else:
            print(f"bench: cell {entry['id']} {outcome['status']} "
                  f"({outcome.get('taxonomy')})", file=sys.stderr)

    counts = run_cells(
        specs, argv_for=_cell_argv, ledger=ledger,
        timeout_s=cell_timeout_s, retries=cell_retries, on_entry=emit)

    rows: list[dict] = []
    signal_cells: list[dict] = []
    cells_status: list[dict] = []
    for e in ledger.doc["cells"]:
        outcome = e["outcome"]
        status = {"id": e["id"], "status": outcome["status"]}
        if outcome["status"] == "ran":
            rows.extend(outcome.get("rows") or [])
            if outcome.get("signals"):
                signal_cells.append(outcome["signals"])
        else:
            status["taxonomy"] = outcome.get("taxonomy")
            status["tail"] = (outcome.get("tail") or "")[-500:]
        cells_status.append(status)
    incomplete = [c["id"] for c in cells_status if c["status"] != "ran"]
    headline = next(
        (r[_rate_key(cpu)] for r in rows
         if r["model"] == "dense" and r["seq_len"] == 2048 and r["prefetch"]),
        None,
    )
    doc = {
        "ok": not incomplete,
        "metric": "bench matrix: {dense,moe} x seq x prefetch tokens/s/chip",
        "value": headline,
        "unit": "tokens/s/chip",
        "vs_baseline": None,
        "matrix": rows,
        "cells": cells_status,
        "incomplete_cells": incomplete,
        "extra": {"rows": len(rows), "ledger": ledger_path,
                  "preflight": pf_doc, "counts": counts,
                  "device": pf_doc.get("device")},
    }
    if incomplete:
        doc["error"] = (f"{len(incomplete)} cell(s) did not run: "
                        + ", ".join(incomplete))
    if signal_cells:
        from automodel_tpu.observability.signals import build_signals

        doc["signals"] = build_signals(signal_cells)
    if cpu:
        doc["extra"]["platform"] = "cpu"
        doc["unit"] = "tokens/s/cpu-device (toy rehearsal, not a device rate)"
    return doc


# ------------------------------------------------------------------ tune mode
def _tune_measure_factory(cpu: bool, nominal_seq: int, plan_cache: dict):
    """Build the per-trial measure() the tuner runner calls: model with the
    trial's backend knobs, AOT compile, a short timed window through the
    overlapped input pipeline at the trial's prefetch depths. Returns raw
    metrics plus a signals-cell snapshot for the ledger."""
    import jax
    import jax.numpy as jnp
    import optax

    from automodel_tpu.data.collate import stack_batches
    from automodel_tpu.data.llm.mock import MockSFTDataset
    from automodel_tpu.data.loader import DataLoader
    from automodel_tpu.data.prefetch import InputPipeline, PrefetchConfig
    from automodel_tpu.models.common.backend import BackendConfig
    from automodel_tpu.models.llama.model import LlamaForCausalLM
    from automodel_tpu.observability import signals as sig
    from automodel_tpu.observability.hlo_costs import (
        compiled_cost_metrics,
        device_specs,
        roofline_metrics,
    )
    from automodel_tpu.observability.memory_plan import compiled_memory_attribution
    from automodel_tpu.ops.losses import masked_cross_entropy
    from automodel_tpu.training.step_scheduler import StepScheduler
    from automodel_tpu.training.train_step import make_train_step

    cfg = _tune_model_config(cpu)
    seq_len = min(nominal_seq, 128) if cpu else nominal_seq
    n_steps = 3 if cpu else 10
    devices = jax.device_count()

    def backend_for(trial) -> BackendConfig:
        kw = dict(dtype="float32") if cpu else dict(
            dtype="bfloat16", attention="flash", attention_segments=False)
        kw["remat_policy"] = trial.remat_policy
        if trial.layout is not None:
            kw["scan_layers"] = trial.layout == "scan"
        if trial.dispatcher is not None:
            kw["dispatcher"] = trial.dispatcher
        return BackendConfig(**kw)

    def measure(trial) -> dict:
        backend = backend_for(trial)
        model = LlamaForCausalLM(cfg, backend)
        micro_batch = int(trial.micro_batch_size or (2 if cpu else 4))

        def forward_loss(p, batch, num_label_tokens):
            logits = model(p, batch["input_ids"], positions=batch["positions"],
                           segment_ids=batch["segment_ids"])
            return masked_cross_entropy(logits, batch["labels"], num_label_tokens)

        optimizer = optax.chain(optax.scale_by_factored_rms(), optax.scale(-1e-5))
        step = jax.jit(make_train_step(forward_loss, optimizer),
                       donate_argnums=(0, 1))
        params = model.init(jax.random.key(0), jnp.dtype(backend.dtype))
        opt_state = jax.jit(optimizer.init)(params)

        rng = np.random.RandomState(0)
        ids = rng.randint(0, cfg.vocab_size, (1, micro_batch, seq_len)).astype(np.int32)
        sample_stack = {
            "input_ids": ids, "labels": ids.copy(),
            "positions": np.ascontiguousarray(np.broadcast_to(
                np.arange(seq_len, dtype=np.int32), ids.shape)),
            "segment_ids": np.ones_like(ids),
        }
        compiled = step.lower(params, opt_state, sample_stack).compile()
        hlo = None
        try:
            hlo = compiled.as_text()
        except Exception:  # noqa: BLE001 — costs/roofline degrade gracefully
            pass
        costs = compiled_cost_metrics(compiled, hlo_text=hlo)
        spec = device_specs(jax.devices()[0].device_kind)
        roof = roofline_metrics(costs, spec) if spec is not None else {}
        attribution = compiled_memory_attribution(compiled)
        peak_gib = (round(attribution["peak_est"] / 2**30, 4)
                    if attribution else None)

        def collate(samples):
            arr = np.asarray([s["input_ids"] for s in samples], np.int32)[:, :seq_len]
            return {
                "input_ids": arr, "labels": arr.copy(),
                "positions": np.ascontiguousarray(np.broadcast_to(
                    np.arange(arr.shape[-1], dtype=np.int32), arr.shape)),
                "segment_ids": np.ones_like(arr),
            }

        ds = MockSFTDataset(vocab_size=cfg.vocab_size, seq_len=seq_len,
                            num_samples=micro_batch * (n_steps + 3), seed=0,
                            item_delay_s=0.002)
        dl = DataLoader(ds, batch_size=micro_batch, collate_fn=collate, seed=0)
        sched = StepScheduler(grad_acc_steps=1, num_epochs=1,
                              max_steps=n_steps + 1, dataloader=dl,
                              handle_sigterm=False)
        pipe = InputPipeline(
            scheduler=sched, dataloader=dl, stack_fn=stack_batches,
            put_fn=jax.device_put,
            config=PrefetchConfig(
                enabled=trial.prefetch_host_depth is not None,
                host_depth=int(trial.prefetch_host_depth or 2),
                device_depth=int(trial.prefetch_device_depth or 2)))
        try:
            first = pipe.get()
            params, opt_state, m = compiled(params, opt_state, first.stack)
            jax.block_until_ready(m["loss"])  # before the clock starts
            done = 0
            t0 = time.perf_counter()
            while done < n_steps:
                item = pipe.get()
                if item is None:
                    break
                params, opt_state, m = compiled(params, opt_state, item.stack)
                done += 1
            jax.block_until_ready(m["loss"])
            dt = time.perf_counter() - t0
        finally:
            pipe.close()
        tps = round(done * micro_batch * seq_len / dt / devices, 1)
        out = {"tps": tps,
               "signals": sig.build_cell(
                   cell={"model": "dense", "seq_len": nominal_seq},
                   roofline=roof or None, costs=costs,
                   memory_plan=plan_cache.get(trial.digest()))}
        if peak_gib is not None:
            out["hbm_gib_peak"] = peak_gib
        return out

    return measure


def _tune_model_config(cpu: bool):
    """The dense cell's dims, shared by the matrix bench and the tuner (the
    tuner rebuilds the model per trial with the trial's backend knobs)."""
    from automodel_tpu.models.llama.model import LlamaConfig

    if cpu:
        return LlamaConfig(
            vocab_size=2048, hidden_size=256, intermediate_size=1024,
            num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=4,
            head_dim=32, max_position_embeddings=512,
        )
    # Llama-3.2-1B dims
    return LlamaConfig(
        vocab_size=128256, hidden_size=2048, intermediate_size=8192,
        num_hidden_layers=16, num_attention_heads=32, num_key_value_heads=8,
        head_dim=64, rope_theta=500000.0, tie_word_embeddings=True,
        max_position_embeddings=131072,
    )


def _tune_bench(cpu: bool, out_dir: str = "tuned",
                baseline_path: str | None = None) -> dict:
    """``--tune``: a pruned, signal-ordered search over the dense smoke cell.

    Emits one ``tuner/*`` JSON row per trial as it lands (the matrix-row
    contract), an atomic resumable ``<out_dir>/tuner_report.json`` ledger, a
    ``tuner_timeline.json`` with one span per trial, the winning trial as
    ``<out_dir>/<cell>.yaml`` (loadable via the recipe's ``tuned_config``
    key), and — when ``baseline_path`` exists — merges the winning cell's
    ``tuned/<cell>/*`` metrics into it through regression.write_baseline so
    the perf gate enforces tuned numbers from then on.
    """
    import os

    import jax
    import jax.numpy as jnp
    import optax

    from automodel_tpu.observability import regression
    from automodel_tpu.observability.events import TraceTimeline
    from automodel_tpu.observability.memory_plan import build_memory_plan
    from automodel_tpu.tuning import SearchSpace, TrialLedger, run_search
    from automodel_tpu.tuning.runner import write_tuned_config

    nominal_seq = 2048
    seq_len = min(nominal_seq, 128) if cpu else nominal_seq
    devices = jax.device_count()
    mesh_name = f"{jax.devices()[0].platform}{devices}"
    cell_name = f"dense_s{nominal_seq}_{mesh_name}"

    space = (SearchSpace.smoke(micro_batch=2) if cpu else SearchSpace(
        microbatch_splits=((4, 1), (2, 2), (1, 4)),
        prefetch_depths=((2, 2), (4, 2), (4, 4)),
        layouts=("scan", "unrolled"),
    ))
    trials = space.enumerate()
    baseline_trial = trials[0]

    # pre-compile memory plans: abstract params/opt-state shapes only — a trial
    # the plan rejects never compiles. The synthetic HBM line sits at 3x the
    # baseline trial's footprint, so the deliberately oversized microbatch
    # split in the smoke space is pruned, not compiled.
    from automodel_tpu.models.common.backend import BackendConfig
    from automodel_tpu.models.llama.model import LlamaForCausalLM

    cfg = _tune_model_config(cpu)
    optimizer = optax.chain(optax.scale_by_factored_rms(), optax.scale(-1e-5))
    plan_cache: dict = {}

    def plan_for(trial, limit_gib):
        backend = BackendConfig(dtype="float32" if cpu else "bfloat16",
                                remat_policy=trial.remat_policy)
        model = LlamaForCausalLM(cfg, backend)
        aparams = model.abstract_params(jnp.dtype(backend.dtype))
        aopt = jax.eval_shape(optimizer.init, aparams)
        return build_memory_plan(
            aparams, aopt,
            micro_batch_size=int(trial.micro_batch_size or (2 if cpu else 4)),
            seq_len=seq_len,
            grad_acc_steps=int(trial.grad_acc_steps or 1),
            model_config=cfg,
            hbm_limit_override_gib=limit_gib,
        )

    base_plan = plan_for(baseline_trial, None)
    limit_gib = round(base_plan.total_bytes * 3 / 2**30, 6)

    def plan_fn(trial):
        plan = plan_for(trial, limit_gib)
        plan_cache[trial.digest()] = plan
        return plan

    # exploration order comes from the cell's analytic bound: one baseline
    # measure (compile + costs + roofline) before the search proper
    measure = _tune_measure_factory(cpu, nominal_seq, plan_cache)
    plan_cache[baseline_trial.digest()] = base_plan
    probe = measure(baseline_trial)
    bound = ((probe.get("signals") or {}).get("analytic") or {}).get("roofline_bound")

    os.makedirs(out_dir, exist_ok=True)
    report_path = os.path.join(out_dir, "tuner_report.json")
    ledger = TrialLedger(report_path,
                         cell={"model": "dense", "seq_len": nominal_seq,
                               "mesh": mesh_name},
                         bound=bound)
    timeline = TraceTimeline(os.path.join(out_dir, "tuner_timeline.json"))

    def metric_sink(row):
        print(json.dumps({"tuner_row": True, **row}), flush=True)

    result = run_search(trials, measure=measure, ledger=ledger,
                        plan_fn=plan_fn, bound=bound, baseline=baseline_trial,
                        timeline=timeline, metric_sink=metric_sink)
    timeline.close()

    winner = result["winner"]
    doc = {
        "ok": True,
        "metric": f"bench tune: pruned search over {cell_name}",
        "value": (winner["outcome"]["metrics"].get("tuner/tps")
                  if winner else None),
        "unit": "tokens/s/chip",
        "vs_baseline": None,
        "tuner": {
            "cell": cell_name,
            "bound": bound,
            "counts": result["counts"],
            "report": report_path,
            "winner": winner["digest"] if winner else None,
            "attribution": (result["attribution"] or {}).get("line"),
        },
        "extra": {"device": str(jax.devices()[0])},
    }
    if cpu:
        doc["extra"]["platform"] = "cpu"
        doc["unit"] = "tokens/s/cpu-device (toy rehearsal, not a device rate)"
        doc["extra"]["measured_seq_len"] = seq_len
    if winner is None:
        doc["ok"] = False
        doc["error"] = "no trial ran to completion"
        return doc

    tuned_path = os.path.join(out_dir, f"{cell_name}.yaml")
    write_tuned_config(tuned_path, cell_name=cell_name, entry=winner,
                       attribution=result["attribution"])
    doc["tuner"]["tuned_config"] = tuned_path

    tuned_metrics = {
        f"tuned/{cell_name}/{k.rsplit('/', 1)[-1]}": v
        for k, v in winner["outcome"]["metrics"].items()
        if k in ("tuner/tps", "tuner/hbm_gib_peak")
    }
    # gate-ready form: load_run_metrics lifts these so the same stdout capture
    # that announced the winner can be gated against the merged baseline
    doc["tuner"]["metrics"] = tuned_metrics
    if baseline_path and os.path.exists(baseline_path):
        regression.write_baseline(
            baseline_path, tuned_metrics, merge=True,
            meta={"source": "bench.py --tune", "cell": cell_name,
                  "winner": winner["digest"],
                  "attribution": (result["attribution"] or {}).get("line")})
        comps = regression.compare(
            tuned_metrics,
            {k: v for k, v in regression.load_baseline(baseline_path).items()
             if k in tuned_metrics})
        doc["tuner"]["baseline"] = baseline_path
        doc["tuner"]["gate"] = ("PASS" if all(c.ok for c in comps)
                                else "FAIL")
    return doc


def _flag_value(argv: list[str], flag: str) -> str | None:
    if flag in argv:
        i = argv.index(flag)
        if i + 1 < len(argv):
            return argv[i + 1]
    return None


def _classify(text: str) -> tuple[str, bool]:
    """``(taxonomy, transient)`` for an error message / traceback tail.

    Delegates to the supervisor's classifier (resilience/supervisor.py), which
    fixes the r05 misclassification: the old substring set here matched
    "UNAVAILABLE"/"initialize backend" anywhere, so a *lowering* error whose
    message merely contained init-looking text (BENCH_r05's
    ``convert_element_type`` failure) retried and fell back to CPU as if the
    chip were absent. The classifier's non-transient markers (setup/compile
    error, lowering frames) override init-looking text — only genuinely
    transient init errors may retry or fall back."""
    from automodel_tpu.resilience.supervisor import classify_error_text

    return classify_error_text(text)


def _transient_backend_error(exc: BaseException) -> bool:
    taxonomy, transient = _classify(repr(exc))
    return transient and taxonomy in ("backend-init", "preemption")


def _init_backend(max_attempts: int = 3) -> str:
    """Attach the JAX backend with bounded retry + exponential backoff
    (``utils/retry.py`` policy curve). A TPU attach can fail transiently while
    a previous holder releases the chips ("Device or resource busy",
    UNAVAILABLE) — sleeping through the handoff beats giving up at once. Only
    errors the taxonomy classifier marks transient retry; anything else is a
    code/compiler bug and raises immediately. On exhaustion the LAST named
    init error raises, and main() routes it into the ``error`` field of the
    guaranteed final JSON line."""
    from automodel_tpu.utils.retry import RetryConfig

    policy = RetryConfig(max_attempts=max_attempts, base_delay_s=1.0,
                         max_delay_s=15.0)
    last: Exception | None = None
    for attempt in range(max(int(max_attempts), 1)):
        try:
            import jax

            return jax.default_backend()  # first real backend touch
        except Exception as exc:  # noqa: BLE001 — filtered just below
            if not _transient_backend_error(exc):
                raise
            last = exc
            if attempt + 1 >= max_attempts:
                break
            d = policy.delay(attempt)
            print(
                f"bench: backend init failed (attempt {attempt + 1}/"
                f"{max_attempts}): {exc!r} — retrying in {d:.1f}s",
                file=sys.stderr,
            )
            time.sleep(d)
    assert last is not None
    raise RuntimeError(
        f"backend init failed after {max_attempts} attempts: {last!r}"
    ) from last


def _canary_dispatch() -> None:
    """One trivial jitted op through the attached backend. A backend that
    initializes but cannot execute (driver/libtpu mismatch, wedged chip) fails
    HERE — unambiguously a backend fault, whatever the exception says — instead
    of deep inside the 1B bench where it is indistinguishable from a code bug."""
    import jax
    import jax.numpy as jnp

    jax.jit(lambda x: x + 1)(jnp.arange(8)).block_until_ready()


def _configure_compile_cache() -> None:
    """Every process of the bench that compiles keeps its cache where the
    recipes do (observability/compile_cache.configure: the machine's
    JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)."""
    from automodel_tpu.observability import compile_cache

    compile_cache.configure()


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    matrix = "--matrix" in argv
    # --dynamics: build the measured step with the per-subtree telemetry
    # reductions in-graph, proving the overhead stays inside the gate
    # tolerance instead of asserting it (docs/observability.md)
    dynamics = "--dynamics" in argv
    # --profile: one traced step per matrix cell -> measured_* gate keys +
    # the signals bundle on the summary doc (matrix mode only)
    profile = "--profile" in argv
    # --tune: the perf-lab loop closes — pruned, signal-ordered search over
    # the dense smoke cell with an auditable resumable ledger (tuning/)
    tune = "--tune" in argv
    tune_dir = _flag_value(argv, "--tune-dir") or "tuned"
    tune_baseline = _flag_value(argv, "--tune-baseline")
    # --ledger RUN_DIR|run_ledger.json: merge the run-lifetime goodput ledger
    # (observability/runledger.py) into the summary doc as gate-able
    # goodput_e2e / badput/* / wasted_steps / recovery_s keys, so one capture
    # gates throughput AND recovery cost (docs/observability.md)
    ledger_path = _flag_value(argv, "--ledger")

    def _emit_doc(doc: dict) -> None:
        if ledger_path:
            try:
                from automodel_tpu.observability import runledger

                doc["ledger"] = runledger.gate_metrics(
                    runledger.load_ledger(ledger_path))
            except Exception as exc:  # noqa: BLE001 — a bad ledger must not
                # sink the bench line; the error is named instead
                doc.setdefault("extra", {})["ledger_error"] = repr(exc)
        print(json.dumps(doc), flush=True)
    # matrix isolation knobs (resilience/harness.py)
    matrix_dir = _flag_value(argv, "--matrix-dir") or "bench_matrix"
    resume = "--resume" in argv
    cell_timeout_s = float(_flag_value(argv, "--cell-timeout") or 900.0)
    cell_retries = int(_flag_value(argv, "--cell-retries") or 1)
    isolate = "--no-isolate" not in argv
    cell = _flag_value(argv, "--cell")
    if "--preflight" in argv or cell:
        # child modes for the per-cell harness: run in THIS process (the
        # harness already isolated us), keep the one-JSON-line contract
        try:
            if "--cpu" in argv:
                import jax

                jax.config.update("jax_platforms", "cpu")
            _configure_compile_cache()
            if "--preflight" in argv:
                from automodel_tpu.resilience.harness import preflight_probe

                doc = preflight_probe()
            else:
                doc = _cell_main(cell, cpu="--cpu" in argv,
                                 dynamics=dynamics, profile=profile)
            print(json.dumps(doc), flush=True)
            return 0 if doc.get("ok") else 1
        except Exception as exc:  # noqa: BLE001 — taxonomy-labeled final line
            import traceback

            tail = traceback.format_exc()[-2000:]
            taxonomy, transient = _classify(repr(exc) + "\n" + tail)
            sys.stderr.write(tail)
            sys.stderr.flush()
            print(json.dumps({"ok": False, "error": repr(exc),
                              "taxonomy": taxonomy, "transient": transient,
                              "tail": tail}), flush=True)
            return 1

    def _matrix(cpu: bool) -> dict:
        if not isolate:
            return _matrix_bench_inline(cpu=cpu, dynamics=dynamics,
                                        profile=profile)
        return _matrix_bench(cpu=cpu, dynamics=dynamics, profile=profile,
                             out_dir=matrix_dir, resume=resume,
                             cell_timeout_s=cell_timeout_s,
                             cell_retries=cell_retries)

    def _fail(error: str, **fields) -> int:
        sys.stderr.flush()
        print(json.dumps({"ok": False, "error": error, **fields}), flush=True)
        return 1

    if "--cpu" in argv:
        # an explicit request for a toy-size rehearsal of the harness, never a
        # stand-in for a measurement: rows say "platform": "cpu"
        if not (matrix or tune):
            return _fail("--cpu rehearses --matrix or --tune at a toy size; the "
                         "1B bench itself runs on a chip only")
        try:
            if not (matrix and isolate):  # the matrix parent stays off JAX
                import jax

                jax.config.update("jax_platforms", "cpu")
                _configure_compile_cache()
            doc = (_tune_bench(cpu=True, out_dir=tune_dir, baseline_path=tune_baseline)
                   if tune else _matrix(cpu=True))
            _emit_doc(doc)
            return 0 if doc.get("ok") else 1
        except Exception as exc:  # noqa: BLE001 — the JSON contract is the point
            return _fail(repr(exc))
    if matrix and isolate:
        # every cell is a child process that needs the chip, and a chip belongs
        # to one process: this parent never initialises a backend. The
        # preflight child and the first cell are the probe.
        doc = _matrix(cpu=False)
        _emit_doc(doc)
        return 0 if doc.get("ok") else 1
    try:
        # retried attach: a transient init failure (chip handoff, UNAVAILABLE)
        # gets backoff before it becomes the error of the final line
        _configure_compile_cache()
        backend = _init_backend()
        if backend == "cpu":
            return _fail("no accelerator attached (default backend is cpu); "
                         "bench.py measures on a chip only", platform="cpu")
        try:
            _canary_dispatch()
        except Exception as exc:  # noqa: BLE001 — any canary failure is a backend fault
            return _fail(f"first-dispatch canary failed: {exc!r}",
                         taxonomy="backend-init")
        if tune:
            doc = _tune_bench(cpu=False, out_dir=tune_dir,
                              baseline_path=tune_baseline)
        else:
            doc = (_matrix(cpu=False)
                   if matrix else _full_bench(dynamics=dynamics))
        _emit_doc(doc)
        return 0 if doc.get("ok") else 1
    except Exception as exc:  # noqa: BLE001
        import traceback

        tail = traceback.format_exc()[-2000:]
        taxonomy, _ = _classify(repr(exc) + "\n" + tail)
        # the final line names the failure class and carries the real
        # traceback tail, not just the repr
        return _fail(repr(exc), taxonomy=taxonomy, tail=tail)


def run_cli(argv: list[str] | None = None) -> int:
    """main() inside the last line of defense for the JSON contract: whatever
    escapes — KeyboardInterrupt, SystemExit from a library, MemoryError —
    still ends stdout with one parseable line instead of a bare traceback
    (BENCH_r05). Split from ``__main__`` so tests can drive the guard
    in-process."""
    try:
        return main(argv)
    except BaseException as exc:  # noqa: BLE001
        import traceback

        traceback.print_exc()
        sys.stderr.flush()
        print(json.dumps({"ok": False, "error": repr(exc)}), flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(run_cli())
