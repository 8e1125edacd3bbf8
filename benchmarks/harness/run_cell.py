"""Drive one cell: set-up, window, output check, result line."""

from __future__ import annotations

import gc
import importlib
import json
import math
import os
import statistics
import sys
import time

import yaml

from benchmarks.harness import check, flops, spec

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Wall-clock stamps of every backend compile of the process."""

    def __init__(self):
        import jax.monitoring

        self.finished_at: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, seconds: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.finished_at.append(time.time())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 < t <= t1 for t in self.finished_at)


class StepTap:
    """Stands where the recipe's metric logger stands, passes every row on, and tells
    the run when a step's loss has reached the host."""

    def __init__(self, inner, on_step):
        self._inner, self._on_step = inner, on_step

    def log(self, step: int, **row) -> None:
        if "loss" in row and "event" not in row:
            self._on_step(step, row)
        self._inner.log(step, **row)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class Run:
    """What the harness learns while the recipe's own loop runs."""

    def __init__(self, cell: spec.Cell, args, recipe, stream, clock: CompileClock,
                 make_params, seed_key):
        import jax
        import jax.numpy as jnp

        self.cell, self.args, self.recipe = cell, args, recipe
        self.stream, self.clock = stream, clock
        self.seconds = float(args.seconds)
        self.warm = int(cell.workload["warm_steps"])
        self.ref_steps = int(cell.workload["reference_steps"])
        self.trace_steps = int(cell.workload.get("trace_steps", 5))
        self.first_losses: list[float] = []
        self.stamps: list[float] = []       # perf_counter at each window step's end
        self.window_losses: list[float] = []
        self.t_open = self.t_close = None
        self.wall_open = self.wall_close = None
        self.buckets: list[tuple[float, float]] = []  # at the window's opening, then a step
        self.grad_sq = self.change_sq = None
        self.trace_dir = None
        self._trace_left = None
        opt = importlib.import_module("benchmarks.optimizers." + cell.recipe["optimizer"]["optimizer"])
        adapter = importlib.import_module("benchmarks.adapters." + cell.family)
        hp = opt.hyper(cell.recipe["optimizer"])
        from benchmarks.harness.optstate import layer_sums

        groups = cell.layer_groups
        self._grad_squares = jax.jit(lambda state, params: layer_sums(
            adapter.to_reference(opt.first_grad_squares(state, params, hp)), groups))
        self._change_squares = jax.jit(lambda params, key: layer_sums(adapter.to_reference(
            jax.tree.map(lambda p, q: jnp.square(p.astype(jnp.float32) - q.astype(jnp.float32)),
                         params, make_params(key))), groups))
        self._seed_key = seed_key

    def _buckets(self) -> tuple[float, float]:
        """Seconds so far in the recipe's ``data_wait`` span (the fetch) and its
        ``device_step`` spans (enqueueing the step, then waiting for its loss)."""
        totals = self.recipe.observability.goodput.totals()  # a bucket renamed is an error
        return float(totals["data_wait"]), float(totals["device_step"])

    def on_step(self, step: int, row: dict) -> None:
        now = time.perf_counter()
        if self.t_close is not None:
            return
        if self.t_open is None:
            import jax

            self.first_losses.append(float(row["loss"]))
            if step == 1:
                self.grad_sq = jax.device_get(
                    self._grad_squares(self.recipe.opt_state, self.recipe.train_params))
            if step == self.ref_steps:
                self.change_sq = jax.device_get(
                    self._change_squares(self.recipe.train_params, self._seed_key))
            if step >= self.warm:
                gc.collect()
                self.buckets.append(self._buckets())
                self.wall_open, self.t_open = time.time(), time.perf_counter()
            return
        self.stamps.append(now)
        self.buckets.append(self._buckets())
        self.window_losses.append(float(row["loss"]))
        if self.args.trace and not self.args.rehearse:
            self._trace(now)
        if now - self.t_open >= self.seconds:
            self.t_close, self.wall_close = now, time.time()
            self.stream.stop.set()

    def _trace(self, now: float) -> None:
        """A profiler trace over ``trace_steps`` steps, a third of the way into the window."""
        import jax

        if self._trace_left is None:
            if now - self.t_open < self.seconds / 3:
                return
            self.trace_dir = os.path.join(self.recipe.output_dir, "trace")
            jax.profiler.start_trace(self.trace_dir)
            self._trace_left = self.trace_steps
        elif self._trace_left > 0:
            self._trace_left -= 1
            if self._trace_left == 0:
                jax.profiler.stop_trace()


def _require_devices(cell: spec.Cell, rehearse: bool):
    import jax

    devices = jax.devices()
    if rehearse:
        return devices[:1]
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: JAX found no TPU (platform {devices[0].platform!r}); "
                         "a cell runs on the chip or not at all")
    if len(devices) != cell.chips:
        raise SystemExit(f"benchmark: {cell.name} needs {cell.chips} chip(s), "
                         f"JAX reports {len(devices)}")
    return devices


def _percentile(values: list[float], q: float) -> float:
    ordered = sorted(values)
    pos = q * (len(ordered) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def run_cell(args, t_start: float) -> int:
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    bench = spec.benchmark_json()
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    cell = spec.Cell(args.workload, tiny=args.rehearse, control=args.control)
    if not args.rehearse:  # the launch environment of the cell's deployment, before JAX starts
        os.environ.update({k: str(v) for k, v in cell.workload.get("env", {}).items()})
    marks = [("start", t_start), ("harness imported", time.perf_counter())]
    devices = _require_devices(cell, args.rehearse)
    marks.append(("jax imported, devices found", time.perf_counter()))
    import jax

    if args.rehearse:  # toy compiles for a CPU are no business of the chip's cache
        jax.config.update("jax_enable_compilation_cache", False)

    from benchmarks.harness import weights

    out_dir = os.path.join(spec.ROOT, args.out, cell.name, f"seed_{args.seed}_trace_{args.trace}")
    os.makedirs(out_dir, exist_ok=True)
    rows_path = os.path.join(out_dir, "training.jsonl")
    if os.path.exists(rows_path):
        os.remove(rows_path)
    cfg_path = os.path.join(out_dir, "recipe.yaml")
    with open(cfg_path, "w") as f:
        yaml.safe_dump(cell.recipe_config(args.seed, out_dir), f)

    clock = CompileClock()
    # the recipe class `automodel finetune llm` resolves to, set up and run in this process
    from automodel_tpu.cli.app import RECIPES
    from automodel_tpu.config.cli_overrides import parse_args_and_load_config

    module = importlib.import_module(RECIPES[("finetune", "llm")].split(":")[0])
    recipe_cls = module.TrainFinetuneRecipeForNextTokenPrediction
    if len(jax.devices()) != cell.chips:  # a rehearsal among the tests' virtual devices

        class recipe_cls(recipe_cls):
            def _build_mesh(self, dist_cfg):
                from automodel_tpu.parallel.mesh import MeshContext

                ctx = MeshContext(**dist_cfg, world_size=cell.chips)
                return ctx, ctx.build_mesh(jax.devices()[:cell.chips])

    recipe = recipe_cls(parse_args_and_load_config(["-c", cfg_path]))
    recipe.setup()
    marks.append(("recipe set up", time.perf_counter()))

    # the benchmark's weights, from the seed, in the program's own tree and shardings
    adapter = importlib.import_module("benchmarks.adapters." + cell.family)
    dtype = cell.recipe["model"]["params_dtype"]
    shardings = jax.tree.map(lambda x: x.sharding, recipe.params)
    make_blocks = weights.maker(cell.reference, cell.model, dtype)
    make_tree = lambda key: adapter.from_reference(  # noqa: E731
        weights.stack_layers(make_blocks(key), cell.layer_groups))
    seed_key = weights.seed_key(args.seed)
    seeded = jax.jit(make_tree, out_shardings=shardings)(seed_key)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), recipe.params)
    got = jax.tree.map(lambda x: (x.shape, x.dtype), seeded)
    if want != got:
        raise RuntimeError(f"the adapter's tree is not the program's: {got} != {want}")
    recipe.params = recipe.train_params = seeded
    del seeded
    marks.append(("weights made", time.perf_counter()))

    stream = recipe.dataloader.dataset
    run = Run(cell, args, recipe, stream, clock, make_tree, seed_key)
    recipe.metric_logger = StepTap(recipe.metric_logger, run.on_step)
    recipe.run_train_validation_loop()
    if run.t_close is None:
        raise RuntimeError("the recipe's loop ended before the window closed")

    # ---- what the window showed
    marks.append(("step compiled and warm steps run", run.t_open))
    print("set-up: " + "; ".join(f"{name} {b - a:.2f} s" for (_, a), (name, b)
                                  in zip(marks, marks[1:])), flush=True)
    steps = len(run.stamps)
    window_s = run.t_close - run.t_open
    intervals = [b - a for a, b in zip([run.t_open, *run.stamps[:-1]], run.stamps)]
    tokens_per_s = steps * cell.tokens_per_step / window_s / cell.chips
    kind = devices[0].device_kind
    memory = [(d.memory_stats() or {}) for d in devices]
    compiled_bytes = _compiled_step_bytes(recipe, strict=not args.rehearse)
    print(f"memory: runtime {json.dumps(memory[0])}; compiled step {compiled_bytes} bytes", flush=True)
    with open(rows_path) as f:
        rows = [json.loads(line) for line in f]
    header = next((r for r in rows if r.get("run_header")), {})
    compiles = clock.between(run.wall_open, run.wall_close)
    data_wait_ms = 1e3 * (run.buckets[-1][0] - run.buckets[0][0]) / max(steps, 1)
    longest = intervals.index(max(intervals))
    wait, device = (1e3 * (b - a) for a, b in zip(run.buckets[longest], run.buckets[longest + 1]))
    print(f"window: {steps} steps in {window_s:.3f} s; step ms median "
          f"{1e3 * statistics.median(intervals):.3f} p90 {1e3 * _percentile(intervals, 0.9):.3f} "
          f"max {1e3 * max(intervals):.3f} (step {longest + 1} of the window: fetch {wait:.1f}, "
          f"enqueue and wait for the loss {device:.1f}, rest on the host "
          f"{1e3 * max(intervals) - wait - device:.1f}); samples beyond p90: {steps // 10}",
          flush=True)

    # ---- free the program, then the reference follows the first steps
    verdict = check.Verdict()
    opt_name = cell.recipe["optimizer"]["optimizer"]
    recipe.params = recipe.train_params = recipe.opt_state = None
    recipe._train_step = recipe._step_executors = recipe._compiled_fns = None
    gc.collect()
    t_ref = time.perf_counter()
    from benchmarks.reference import train as reference

    generator = importlib.import_module("benchmarks.generators." + cell.generator)
    vocab = cell.model["vocab_size"]
    batches = [generator.batch(cell.traffic_params, vocab, args.seed, step,
                               cell.micro_batch * cell.grad_acc)
               for step in range(1, run.ref_steps + 1)]
    ref = reference.follow(cell.reference, cell.model, args.seed, batches, opt_name,
                           cell.recipe["optimizer"], params_dtype=dtype)
    print(f"reference: {run.ref_steps} steps in {time.perf_counter() - t_ref:.1f} s", flush=True)

    limits = cell.limits
    for i, (mine, theirs) in enumerate(zip(run.first_losses, ref["losses"]), 1):
        verdict.at_most(f"loss_step_{i}_gap", abs(mine - theirs),
                        limits["loss_first" if i == 1 else "loss_later"],
                        f"program {mine:.5f} reference {theirs:.5f}")
    # the embedding's gradient is a scatter-add of one row a token, accumulated in the
    # parameters' type: under a Zipf law its norm is a number of its own (PERF.md)
    is_embed = lambda name: name == "embed"  # noqa: E731
    gap, where = check.norm_gap(run.grad_sq, ref["grad_sq"], lambda n: not is_embed(n))
    verdict.at_most("first_gradient_norm_gap", gap, limits["grad_norm"], f"worst: {where}")
    gap, where = check.norm_gap(run.grad_sq, ref["grad_sq"], is_embed)
    verdict.at_most("embed_gradient_norm_gap", gap, limits["embed_grad_norm"], where)
    # leaves whose change over the first steps swings from seed to seed by its nature are
    # named in the cell's file, printed, and not compared (PERF.md: the MoE router)
    left_out = set(limits.get("param_change_left_out", ()))
    gap, where = check.norm_gap(run.change_sq, ref["change_sq"], lambda n: n not in left_out)
    verdict.at_most(f"parameter_change_norm_gap_after_{run.ref_steps}", gap,
                    limits["param_change"], f"worst: {where}")
    if left_out:
        gap, where = check.norm_gap(run.change_sq, ref["change_sq"], lambda n: n in left_out)
        print(f"not compared: parameter change of {sorted(left_out)}: {gap:.6g} ({where})", flush=True)
    failed = sum(not math.isfinite(x) for x in run.window_losses)
    verdict.at_most("non_finite_losses", failed, 0)
    entropy = generator.loss_floor(cell.traffic_params, vocab)
    tail = statistics.fmean(run.window_losses[-10:])
    verdict.at_least("last_ten_losses_mean_minus_entropy", tail - entropy,
                     -limits["entropy_slack"], f"H = {entropy:.4f}")
    # the fall is read at the window's median loss, not at its last ten: where the cell's
    # optimizer lets the loss spike for ten to twenty steps (PERF.md, PR 27: the MoE cell
    # from about step 102 on), a mean of the last ten reads the spike that falls there
    settled = check.settled_loss(run.window_losses)
    verdict.at_least("loss_fall_first_step_to_window_median", run.first_losses[0] - settled,
                     limits["loss_fall"], f"first {run.first_losses[0]:.4f} median {settled:.4f}")
    verdict.at_most("compiles_in_window", compiles, 0)
    if not args.rehearse:
        from automodel_tpu.ops import kernels

        try:
            kernels.require_compiled(header.get("kernels", {}), **cell.config["expected_kernels"])
            problem = ""
        except kernels.KernelResolutionError as e:
            problem = str(e)
        verdict.at_most("kernels_that_gave_way", float(bool(problem)), 0, problem)

    # ---- the result line
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": _memory_peak(memory, compiled_bytes)}
    measured = {
        "tokens_per_s_per_chip": (tokens_per_s, "tokens/s/chip"),
        "step_ms_p90": (1e3 * _percentile(intervals, 0.9), "ms"),
        "setup_s": (run.t_open - t_start, "s"),
    }
    readings = {"cell": cell, "run": run, "steps": steps, "window_s": window_s,
                "data_wait_ms": data_wait_ms, "compiles_in_window": compiles,
                "compiled_step_bytes": compiled_bytes, "device_kind": kind,
                "rehearse": args.rehearse}
    result = {"correct": verdict.correct, "attempted": steps, "failed": failed}
    if not args.rehearse:
        from benchmarks.harness.peaks import peaks

        per_token = flops.flops_per_token(cell.reference, cell.model, cell.seq_len)
        measured["mfu"] = (100.0 * tokens_per_s * per_token / peaks(kind)["bf16_flops"], "%")
    if args.trace:
        if not args.rehearse:
            from benchmarks.harness import trace

            if run._trace_left != 0:
                raise RuntimeError(f"the window closed before {run.trace_steps} steps were "
                                   "traced from a third of the way in: give it more seconds")
            planes = trace.load(trace.newest_xplane(run.trace_dir))
            with open(os.path.join(out_dir, "trace_summary.txt"), "w") as f:
                f.write(trace.summary(planes))
            readings["trace"] = trace.reduce_planes(planes)
            device["busy_s"] = readings["trace"]["busy_s"]
            device["window_s"] = readings["trace"]["window_s"]
            result["breakdown"] = readings["trace"]["breakdown"]
        metrics = {}
        for entry in cell.per_layer:
            reader = importlib.import_module("benchmarks.metrics." + entry["name"])
            value = reader.read(readings)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        metrics = {e["name"]: {"value": measured[e["name"]][0], "unit": e["unit"]}
                   for e in cell.end_to_end if e["name"] in measured}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = verdict.as_dict()  # last in the line: each number beside its limit
    print(json.dumps(result), flush=True)
    print(verdict.lines(), file=sys.stderr, flush=True)  # and the last lines on standard error
    return 0


def _memory_peak(memory: list[dict], compiled_bytes: int) -> int:
    """Peak on the fullest chip. The TPU runtime keeps a compiled program's temporaries
    in a reserved region that ``peak_bytes_in_use`` leaves out (9.1 GB against a step
    compiled to 13.5 GB, PERF.md PR 24): the peak is the two together. A backend that
    reports neither falls back to the compiler's count of the step."""
    peaks = [int(m.get("peak_bytes_in_use", 0)) + int(m.get("peak_bytes_reserved", 0))
             for m in memory]
    return max(peaks) if max(peaks) > 0 else compiled_bytes


def _compiled_step_bytes(recipe, strict: bool) -> int:
    """Arguments + outputs + temporaries - aliased, of the compiled step: the compiler's
    count of what the step holds at once. It is read from the program's private
    ``_step_executors[..]._variants``. On the chip (``strict``) a step that is not found
    there, or has no analysis, is an error and not a metric left out: a rename in the
    program, or a step that fell back to jit, stops the run instead of thinning the
    line. A rehearsal's backend may lack the analysis; its 0 leaves ``step_hbm_gib`` out."""
    total = 0
    try:
        for executor in recipe._step_executors.values():
            for compiled in executor._variants.values():
                m = compiled.memory_analysis()
                total = max(total, int(m.argument_size_in_bytes + m.output_size_in_bytes
                                       + m.temp_size_in_bytes - m.alias_size_in_bytes))
    except Exception as e:
        if strict:
            raise RuntimeError("the compiled step's memory analysis cannot be read from "
                               "recipe._step_executors[..]._variants") from e
        print(f"no memory analysis of the compiled step: {e!r}", flush=True)
    if strict and total <= 0:
        raise RuntimeError("recipe._step_executors holds no compiled step")
    return total
