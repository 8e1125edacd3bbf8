"""The step loop's order (docs/performance.md "The step loop's order"): between the
dispatch of step N and the first read of its scalars the loop takes step N+1's batch,
reckons the row's learning rate on the host and then pulls the row's scalars in ONE
transfer; the row is written, and step N+1 enqueued, only after that.

Tiny dense and MoE recipes run on one virtual device with everything the loop touches
recorded in one list of events: the spans (``Observability.track``), the pipeline's
``get`` / ``consume``, the logger's rows, every ``jax.device_get`` and every other read
of a device array (``ArrayImpl._value``, which ``float()``, ``int()``, ``bool()`` and
``np.asarray`` all go through). The rows are held against a plain loop in the old order
(fetch, step, pull, schedule on the device), driven by hand on a second recipe.
"""

import json
import os
import shutil
import textwrap
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.config.loader import load_config
from automodel_tpu.recipes.llm.train_ft import TrainFinetuneRecipeForNextTokenPrediction

_MODELS = {
    "dense": """
        architectures: [LlamaForCausalLM]
        vocab_size: 64
        hidden_size: 32
        intermediate_size: 64
        num_hidden_layers: 1
        num_attention_heads: 2
        num_key_value_heads: 1
        max_position_embeddings: 64""",
    "moe": """
        architectures: [Qwen3MoeForCausalLM]
        vocab_size: 64
        hidden_size: 32
        intermediate_size: 48
        moe_intermediate_size: 16
        num_hidden_layers: 1
        num_attention_heads: 2
        num_key_value_heads: 1
        head_dim: 16
        max_position_embeddings: 64
        num_experts: 4
        num_experts_per_tok: 2
        norm_topk_prob: true
        router_aux_loss_coef: 0.01""",
}

# what a log row of the parent commit (5940a76) carried for these configurations on the
# CPU (`step` and `ts` are the logger's); the new order adds `input_ready_ahead` and no
# other. A MoE row adds the balance rows of its `expert_load` under `moe_load/` and again
# under `moe/`, whose names carry expert ids, and these
_PARENT_KEYS = {"loss", "grad_norm", "lr", "num_label_tokens", "step_time_s", "tps",
                "tps_per_chip", "tflops_per_chip", "compile_time_s", "goodput",
                "goodput_wall_s", "goodput/checkpoint", "goodput/compile", "goodput/data_wait",
                "goodput/device_step", "goodput/eval", "goodput/idle", "goodput/restore",
                "goodput/rollback"}
_PARENT_MOE_KEYS = {"moe/aux_loss", "moe/aux_loss_ema", "moe/aux_loss_trend",
                    "moe/routing_entropy", "moe/routing_entropy_min",
                    "moe/tokens_per_sec_per_chip"}


class _OneDeviceRecipe(TrainFinetuneRecipeForNextTokenPrediction):
    def _build_mesh(self, dist_cfg):
        from automodel_tpu.parallel.mesh import MeshContext

        ctx = MeshContext(**dist_cfg, world_size=1)
        return ctx, ctx.build_mesh(jax.devices()[:1])


def _cfg(tmp, model, *, max_steps=10, log_every=1, grad_acc=1, prefetch=False, ckpt_every=0,
         num_samples=512, extra=""):
    tmp.mkdir(parents=True, exist_ok=True)
    text = f"""
    seed: 11
    output_dir: {tmp}/out
    model:
      config:{_MODELS[model]}
    distributed:
      dp_shard: 1
    backend:
      dtype: float32
    dataset:
      _target_: automodel_tpu.data.llm.mock.MockSFTDataset
      vocab_size: 64
      seq_len: 16
      num_samples: {num_samples}
      seed: 0
      pattern: arith
    micro_batch_size: 4
    seq_len: 16
    step_scheduler:
      grad_acc_steps: {grad_acc}
      max_steps: {max_steps}
      num_epochs: 1
      log_every_steps: {log_every}
      handle_sigterm: false
      ckpt_every_steps: {ckpt_every}
    optimizer:
      lr: 1.0e-2
      weight_decay: 0.0
      max_grad_norm: 1.0
    lr_scheduler:
      lr_warmup_steps: 3
      lr_decay_steps: 20
      min_lr: 1.0e-4
    dataloader:
      prefetch:
        enabled: {str(prefetch).lower()}
    checkpoint:
      enabled: {str(bool(ckpt_every)).lower()}
      checkpoint_dir: {tmp}/ckpt
    {extra}
    """
    path = tmp / "cfg.yaml"
    path.write_text(textwrap.dedent(text))
    return load_config(path)


def _checksum(tree) -> float:
    return float(sum(np.abs(np.asarray(x, np.float64)).sum() for x in jax.tree.leaves(tree)))


class _Recording:
    """One run of the recipe's own loop, with what it did in order."""

    def __init__(self, cfg):
        self.events: list[tuple] = []
        self.rows: dict[int, dict] = {}
        self.state_in_log: dict[int, float] = {}
        self.consumed: list[tuple[int, np.ndarray]] = []  # (step, the batch's input_ids)
        self._quiet = 0  # reads the recorder itself makes, or jax.device_get's own
        self._host_of: dict[int, np.ndarray] = {}
        self._stacks: list = []  # keeps ids unique
        self.recipe = recipe = _OneDeviceRecipe(cfg).setup()
        self._wrap_pipeline(recipe)
        self._wrap_logger(recipe)
        track = recipe.observability.track

        @contextmanager
        def tracked(name, step=None, bucket=None):
            self.events.append(("enter", name, step))
            with track(name, step=step, bucket=bucket):
                yield
            self.events.append(("exit", name, step))

        recipe.observability.track = tracked

    def _wrap_pipeline(self, recipe):
        put, build = recipe._device_put_stack, recipe._build_input_pipeline

        def put_stack(stack):
            out = put(stack)
            self._host_of[id(out)] = np.array(stack["input_ids"])
            self._stacks.append(out)
            return out

        def build_pipeline():
            pipe = build()
            get, consume = pipe.get, pipe.consume

            def recorded_get(ahead=False):
                item = get(ahead=ahead)
                self.events.append(("get", None if item is None else item.step, ahead))
                return item

            def recorded_consume(item):
                self.consumed.append((item.step, self._host_of[id(item.stack)]))
                consume(item)

            pipe.get, pipe.consume = recorded_get, recorded_consume
            return pipe

        recipe._device_put_stack, recipe._build_input_pipeline = put_stack, build_pipeline

    def _wrap_logger(self, recipe):
        inner, rec = recipe.metric_logger, self

        class Tap:
            def log(self, step, **row):
                if "loss" in row and "event" not in row:
                    rec.events.append(("log_enter", step))
                    rec.rows[step] = row
                    rec._quiet += 1
                    rec.state_in_log[step] = (_checksum(recipe.train_params)
                                              + _checksum(recipe.opt_state))
                    rec._quiet -= 1
                inner.log(step, **row)
                if "loss" in row and "event" not in row:
                    rec.events.append(("log_exit", step))

            def __getattr__(self, name):
                return getattr(inner, name)

        recipe.metric_logger = Tap()

    def run(self):
        from jax._src.array import ArrayImpl

        mp = pytest.MonkeyPatch()
        device_get, value = jax.device_get, ArrayImpl._value

        def counted_device_get(tree):
            if not self._quiet:
                self.events.append(("device_get",))
            self._quiet += 1
            try:
                return device_get(tree)
            finally:
                self._quiet -= 1

        def counted_value(arr):
            if not self._quiet:
                self.events.append(("host_read",))
            return value.fget(arr)

        mp.setattr(jax, "device_get", counted_device_get)
        mp.setattr(ArrayImpl, "_value", property(counted_value))
        try:
            self.recipe.run_train_validation_loop()
        finally:
            mp.undo()
        return self

    def index(self, event) -> int:
        return self.events.index(event)

    def final_state(self) -> float:
        return _checksum(self.recipe.train_params) + _checksum(self.recipe.opt_state)


def _old_order_rows(cfg, steps: int) -> dict[int, dict]:
    """The parent's order on a recipe of its own: fetch, step, pull each scalar, the
    schedule evaluated on the device."""
    recipe = _OneDeviceRecipe(cfg).setup()
    pipe = recipe._build_input_pipeline()
    params, opt_state = recipe.train_params, recipe.opt_state
    rows = {}
    with recipe.mesh:
        for _ in range(steps):
            item = pipe.get()
            params, opt_state, m = recipe._train_step(params, opt_state, item.stack)
            rows[item.step] = dict(
                loss=float(m["loss"]), grad_norm=float(m["grad_norm"]),
                num_label_tokens=int(m["num_label_tokens"]),
                lr=float(recipe.lr_schedule(jnp.int32(item.step))),
                expert_load=np.asarray(m["expert_load"]) if "expert_load" in m else None,
            )
    pipe.close()
    return rows


# (model, log_every_steps, grad_acc_steps, dataloader.prefetch.enabled)
_CASES = [("dense", 1, 1, False), ("dense", 3, 2, True), ("dense", 1, 2, False),
          ("dense", 3, 1, True), ("moe", 1, 2, False), ("moe", 3, 1, True),
          ("moe", 1, 1, True), ("moe", 3, 2, False)]
_IDS = [f"{m}-log{le}-acc{ga}-{'prefetch' if pf else 'sync'}" for m, le, ga, pf in _CASES]


@pytest.fixture(scope="module")
def runs(tmp_path_factory, cpu_devices):
    """Every case's recorded run, made on first use and kept for the module."""
    made: dict = {}
    tmp = tmp_path_factory.mktemp("loop_order")

    def get(case):
        if case not in made:
            model, log_every, grad_acc, prefetch = case
            cfg = _cfg(tmp / f"run{len(made)}", model, log_every=log_every,
                       grad_acc=grad_acc, prefetch=prefetch)
            made[case] = _Recording(cfg).run()
        return made[case]

    return get


@pytest.fixture(scope="module")
def old_order(tmp_path_factory, cpu_devices):
    made: dict = {}
    tmp = tmp_path_factory.mktemp("old_order")

    def get(model, grad_acc):
        if (model, grad_acc) not in made:
            cfg = _cfg(tmp / f"ref{len(made)}", model, grad_acc=grad_acc)
            made[model, grad_acc] = _old_order_rows(cfg, 10)
        return made[model, grad_acc]

    return get


# ---------------------------------------------------------------- (a) the order
_every_step_logged = pytest.mark.parametrize(
    "case", [c for c in _CASES if c[1] == 1], ids=[i for i in _IDS if "log1" in i])


@_every_step_logged
def test_next_batch_is_taken_between_dispatch_and_first_read(runs, case):
    rec = runs(case)
    for step in range(2, 10):  # step 1 compiles
        dispatched = rec.index(("exit", "train_step", step))
        taken = rec.index(("get", step + 1, True))
        first_read = next(i for i in range(dispatched, len(rec.events))
                          if rec.events[i][0] in ("device_get", "host_read"))
        assert dispatched < taken < first_read, (step, rec.events[dispatched:first_read + 1])
        # and the row's learning rate is reckoned before that read, with no read of its own
        assert taken < rec.index(("exit", "lr_schedule", step)) < first_read


@_every_step_logged
def test_no_step_is_enqueued_before_the_row_before_it_is_written(runs, case):
    rec = runs(case)
    for step in range(1, 10):
        nxt = ("enter", "train_step", step + 1)
        assert rec.index(("log_enter", step)) < rec.index(("log_exit", step)) < rec.index(nxt)
        # nor taken out of the loop's hand: step N+1 is consumed after N's row too
        assert [s for s, _ in rec.consumed] == list(range(1, 11))


@pytest.mark.parametrize("model", ["dense", "moe"])
def test_inside_log_the_state_is_that_steps(runs, tmp_path, model):
    """What `benchmarks/harness/run_cell.Run.on_step` leans on: at `log(step=N)` the
    recipe's parameters and optimizer state are the state after step N."""
    rec = runs(_CASES[0] if model == "dense" else _CASES[4])
    grad_acc = 1 if model == "dense" else 2
    short = _Recording(_cfg(tmp_path, model, max_steps=3, grad_acc=grad_acc)).run()
    assert rec.state_in_log[3] == short.final_state()
    assert short.state_in_log[3] == short.final_state()
    assert rec.state_in_log[10] == rec.final_state()
    assert rec.state_in_log[3] != rec.state_in_log[4]


# ---------------------------------------------------------------- (b) the rows
@pytest.mark.parametrize("case", _CASES, ids=_IDS)
def test_rows_equal_the_old_orders(runs, old_order, case):
    from automodel_tpu.moe.metrics import compute_load_balance_metrics

    model, log_every, grad_acc, prefetch = case
    rec, ref = runs(case), old_order(model, grad_acc)
    assert sorted(rec.rows) == [s for s in range(1, 11) if s % log_every == 0]
    for step, row in rec.rows.items():
        want = ref[step]
        assert row["loss"] == want["loss"] and row["grad_norm"] == want["grad_norm"], step
        assert row["num_label_tokens"] == want["num_label_tokens"]
        assert abs(np.float32(row["lr"]) - np.float32(want["lr"])) <= np.spacing(
            np.float32(want["lr"])), (step, row["lr"], want["lr"])
        keys = _PARENT_KEYS | {"input_ready_ahead"} | ({"prefetch_depth"} if prefetch else set())
        if model == "moe":
            balance = compute_load_balance_metrics(want["expert_load"], mode=rec.recipe.moe_metrics_mode)
            assert balance and {k: row[k] for k in balance} == balance, step
            keys |= set(balance) | {k.replace("moe_load/", "moe/") for k in balance}
            keys |= _PARENT_MOE_KEYS
            if row["step_time_s"] is None:  # the compiling step has no rate yet
                keys.discard("moe/tokens_per_sec_per_chip")
        assert set(row) == keys, (step, set(row) ^ keys)


# ---------------------------------------------------------------- (d) the counter
@pytest.mark.parametrize("case", _CASES, ids=_IDS)
def test_input_ready_ahead_is_0_on_a_passes_first_step_and_1_after(runs, case):
    rows = runs(case).rows
    assert {s: r["input_ready_ahead"] for s, r in rows.items()} == {
        s: int(s > 1) for s in rows}


# ---------------------------------------------------------------- (e) one read
@pytest.mark.parametrize("case", _CASES, ids=_IDS)
def test_one_host_read_a_logged_step_and_none_on_the_others(runs, case):
    rec, log_every = runs(case), case[1]
    for step in range(2, 11):
        lo, hi = rec.index(("enter", "train_step", step)), rec.index(("exit", "step_end", step))
        reads = [e[0] for e in rec.events[lo:hi] if e[0] in ("device_get", "host_read")]
        assert reads == (["device_get"] if step % log_every == 0 else []), (step, reads)
        if reads:  # the one transfer is the span `loss_pull`'s
            pull = rec.index(("enter", "loss_pull", step))
            assert rec.events[pull + 1] == ("device_get",)


# ---------------------------------------------------------------- (c) resume, rollback, end
def _batches_in_order(tmp, n, **kw):
    """The first ``n`` steps' input_ids of a fresh loader, from a recipe never run."""
    recipe = _OneDeviceRecipe(_cfg(tmp / "fresh", "dense", **kw)).setup()
    pipe = recipe._build_input_pipeline()
    out = [np.asarray(pipe.get().stack["input_ids"]) for _ in range(n)]
    pipe.close()
    return out


@pytest.mark.parametrize("prefetch", [False, True], ids=["sync", "prefetch"])
def test_a_save_with_a_batch_in_hand_resumes_bit_for_bit(tmp_path, cpu_devices, prefetch):
    """Step 4's checkpoint is written while the loop holds step 5's batch: the live
    scheduler and loader stand past it, and what is saved is the consumed position."""
    kw = dict(max_steps=8, ckpt_every=4, prefetch=prefetch)
    whole = _Recording(_cfg(tmp_path, "dense", **kw)).run()
    taken, saved = whole.index(("get", 5, True)), whole.index(("enter", "checkpoint", 4))
    assert taken < saved < whole.index(("enter", "train_step", 5))
    with open(tmp_path / "ckpt" / "step_4" / "client.json") as f:
        state = json.load(f)
    assert state["step_scheduler"]["step"] == 4 and state["dataloader"]["cursor"] == 4

    shutil.rmtree(tmp_path / "ckpt" / "step_8")
    (tmp_path / "ckpt" / "latest").unlink()
    os.symlink("step_4", tmp_path / "ckpt" / "latest")
    resumed = _Recording(_cfg(tmp_path, "dense", **kw))
    assert resumed.recipe.step_scheduler.step == 4
    resumed.run()
    assert sorted(resumed.rows) == [5, 6, 7, 8]
    for step in (5, 6, 7, 8):
        for key in ("loss", "grad_norm", "lr", "num_label_tokens"):
            assert resumed.rows[step][key] == whole.rows[step][key], (step, key)
    for (s1, b1), (s2, b2) in zip(whole.consumed[4:], resumed.consumed, strict=True):
        assert s1 == s2 and np.array_equal(b1, b2)
    assert resumed.final_state() == whole.final_state()


def test_the_end_of_data_ends_the_loop_after_the_last_steps_row(tmp_path, cpu_devices):
    # 10 batches of 4 samples, three a step: three whole steps and a trailing one
    kw = dict(max_steps="null", grad_acc=3, num_samples=40)
    rec = _Recording(_cfg(tmp_path, "dense", ckpt_every=2, **kw)).run()
    assert sorted(rec.rows) == [1, 2, 3, 4]
    end = rec.index(("get", None, True))  # the look-ahead that found the data at its end
    assert rec.index(("exit", "train_step", 4)) < end < rec.index(("log_enter", 4))
    assert rec.index(("log_exit", 4)) < rec.index(("enter", "checkpoint", 4))
    assert rec.events.count(("get", None, True)) == 1
    want = _batches_in_order(tmp_path, 4, **kw)
    assert [s for s, _ in rec.consumed] == [1, 2, 3, 4]
    for (_, got), ids in zip(rec.consumed, want, strict=True):
        assert np.array_equal(got, ids)
    assert rec.consumed[3][1].shape[0] == 1  # the trailing step's one microbatch


def test_a_rollback_drops_the_batch_in_hand_and_feeds_every_other_batch_once(
        tmp_path, cpu_devices):
    extra = textwrap.dedent("""\
    resilience:
      enabled: true
      anomaly: {window: 20, min_history: 5}
      max_skipped_updates: 0
      rollback: {max_rollbacks: 2, skip_steps: 0}
      chaos:
        enabled: true
        nan_grad_steps: [6]
    """).replace("\n", "\n    ")
    rec = _Recording(_cfg(tmp_path, "dense", ckpt_every=4, extra=extra)).run()
    assert 6 not in rec.rows and max(rec.rows) == 10
    # step 7's batch was in hand when step 6 rolled back: it went with the pipeline, the
    # restored cursor skipped the offending window, and step 7 got that same batch anew
    assert [s for s, _ in rec.consumed] == [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
    assert [e[1] for e in rec.events if e[0] == "get"].count(7) == 2
    for (_, got), ids in zip(rec.consumed, _batches_in_order(tmp_path, 10), strict=True):
        assert np.array_equal(got, ids)
    # a pass's first step has nothing fetched ahead; with resilience on, the verdict's
    # scalars and the row's are still one transfer
    assert {s: r["input_ready_ahead"] for s, r in rec.rows.items()} == {
        s: int(s not in (1, 7)) for s in rec.rows}
    for step in (2, 3, 5, 8):
        lo, hi = rec.index(("enter", "train_step", step)), rec.index(("exit", "step_end", step))
        inside = [e for e in rec.events[lo:hi] if e[0] != "get"]
        reads = [e[0] for e in inside if e[0] in ("device_get", "host_read")]
        assert reads == ["device_get"], (step, reads)
        assert inside.index(("enter", "step_hooks", step)) < inside.index(("device_get",)) \
            < inside.index(("exit", "step_hooks", step))


@pytest.mark.parametrize("lands", ["before_the_look_ahead", "after_the_look_ahead"])
def test_a_sigterm_is_agreed_at_the_step_it_lands_in(tmp_path, cpu_devices, lands):
    """The synchronous fetch stops on the local flag alone and the agreed check stays at
    ``step_end``: whether the look-ahead came back empty or with step 4's batch, the run
    saves at step 3 with the consumed position and ends there."""
    rec = _Recording(_cfg(tmp_path, "dense", ckpt_every=50))
    sched, track = rec.recipe.step_scheduler, rec.recipe.observability.track

    @contextmanager
    def tracked(name, step=None, bucket=None):
        if step == 3 and name == {"before_the_look_ahead": "train_step",
                                  "after_the_look_ahead": "step_hooks"}[lands]:
            sched._sigterm.set()
        with track(name, step=step, bucket=bucket):
            yield

    rec.recipe.observability.track = tracked
    rec.run()
    assert sorted(rec.rows) == [1, 2, 3]
    took_4 = ("get", 4, True) in rec.events
    assert took_4 == (lands == "after_the_look_ahead")
    assert os.path.realpath(tmp_path / "ckpt" / "latest").endswith("step_3")
    with open(tmp_path / "ckpt" / "step_3" / "client.json") as f:
        state = json.load(f)
    assert state["step_scheduler"]["step"] == 3 and state["dataloader"]["cursor"] == 3
