"""From a traced run to device time per layer kind and kernel, and to the device's idle
time split by the program span the host was in.

What the program lays down (PR 25, looked at by hand in a TPU v5e trace):

- every Pallas kernel has a ``name=``; its ``XLA Ops`` event is ``%<name>.<n> = ...``
  (``%flash_attention_fwd.19``, ``%linear_ce_bwd_dw.2``);
- every layer kind runs under a ``jax.named_scope`` label. A v5e ``XLA Ops`` event carries
  no ``op_name`` among its stats (only offsets and durations), so the program writes the
  compiled step's instruction -> ``op_name`` table beside the run's files at compile,
  ``<output_dir>/step_scopes.json``; the join is on the instruction name. Backward and
  recomputed operations keep the label inside ``transpose(jvp(attention))``: matched by
  path component;
- every part of a loop iteration runs inside a span (``Observability.track``) that enters
  ``jax.profiler.TraceAnnotation(name, step=...)`` (``StepTraceAnnotation`` for
  ``train_step``), so it lies on the host plane on the device trace's clock.

The window is ``harness/trace.reduce_planes``'s: first to last start of the step's module.
Busy time is the union of the device's operation intervals in it; every idle interval goes
to the innermost program span that covers it. Device time is kept twice: folded into the
seven layer kinds (``layer_s``, which add up to the busy time) and per label as the program
spells it (``label_s``, for a reader of one scope: ``scope_ms``). Seconds throughout;
``steps`` whole steps lie in the window.
"""

from __future__ import annotations

import json
import os
import re

from benchmarks.harness import trace as trace_lib

# the program's spans, docs/observability.md "Spans"
PROGRAM_SPANS = ("data_wait", "train_step", "step_hooks", "loss_pull", "log_row",
                 "lr_schedule", "eval", "checkpoint", "rollback", "step_end", "compile")
# named-scope label -> layer kind; a label that starts with ``moe`` or ``ep_`` is the
# MoE block's (moe, moe_gate, moe_dispatch, moe_experts, moe_combine, ep_dispatch, ...)
_LAYER_OF_LABEL = {"attention": "attention", "mla_attention": "attention", "mlp": "mlp",
                   "lm_head_loss": "lm_head_loss", "optimizer": "optimizer", "embed": "embed",
                   "layer_stack": "layer_stack"}
_LABEL_RE = re.compile(r"[A-Za-z_]\w*")


def layer_of(instruction: str, op_name: str | None) -> str | None:
    """The layer kind of one device operation: the innermost known label on its
    ``op_name`` path, else what its instruction name says, else None (unscoped)."""
    for label in reversed(_LABEL_RE.findall(op_name or "")):
        if label in _LAYER_OF_LABEL:
            return _LAYER_OF_LABEL[label]
        if label.startswith(("moe", "ep_")):
            return "moe"
    # the TPU compiler rewrites ``lax.ragged_dot`` into custom calls named ``ragged-dot-*``
    # and drops their ``op_name`` (it reads "ragged-dot-none"): they are the routed
    # experts' GEMMs, which the program calls under ``moe/moe_experts``
    return "moe" if instruction.startswith("ragged-dot") else None


def instruction(short: str) -> str:
    """``%fusion.3 fusion`` (``harness/trace.short_name``) -> ``fusion.3``."""
    return short.partition(" ")[0].lstrip("%")


def kernel(short: str) -> str:
    """``%linear_ce_fwd.2 custom-call`` -> ``linear_ce_fwd``."""
    name, _, suffix = instruction(short).rpartition(".")
    return name if suffix.isdigit() else instruction(short)


def record(xplane_path: str, scopes_path: str | None) -> dict:
    """What the reduction needs of one trace, as plain lists (the form the recorded
    fixture is kept in): per device its module runs and operations, the program's spans
    with their step, and the ``op_name`` of every instruction that ran."""
    from jax.profiler import ProfileData

    devices, spans = [], []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: [[trace_lib.short_name(ev.name), float(ev.start_ns),
                                  float(ev.duration_ns)] for ev in line.events]
                     for line in plane.lines
                     if line.name in (trace_lib._OPS_LINE, trace_lib._MODULES_LINE)}
            if lines.get(trace_lib._OPS_LINE):
                devices.append({"name": plane.name, "ops": lines[trace_lib._OPS_LINE],
                                "modules": lines.get(trace_lib._MODULES_LINE, [])})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in PROGRAM_SPANS:
                        stats = dict(ev.stats)
                        step = stats.get("step_num", stats.get("step"))
                        spans.append([ev.name, None if step is None else int(step),
                                      float(ev.start_ns), float(ev.duration_ns)])
    op_names = None
    if scopes_path is not None and os.path.exists(scopes_path):
        with open(scopes_path) as f:
            table = json.load(f)
        ran = {instruction(name) for dev in devices for name, _, _ in dev["ops"]}
        op_names = {k: v for k, v in table.items() if k in ran}
    return {"devices": sorted(devices, key=lambda d: d["name"]), "spans": spans,
            "op_names": op_names}


def _innermost(spans: list[tuple[str, float, float]], a: float, b: float) -> dict[str, float]:
    """The idle interval [a, b) cut at every span boundary; each piece goes to the
    covering span that started last (spans nest, so that is the innermost)."""
    cuts = sorted({a, b, *(x for _, s, e in spans for x in (s, e) if a < x < b)})
    out: dict[str, float] = {}
    for lo, hi in zip(cuts, cuts[1:]):
        covering = [(s, name) for name, s, e in spans if s <= lo and hi <= e]
        name = max(covering)[1] if covering else "unattributed"
        out[name] = out.get(name, 0.0) + (hi - lo)
    return out


def reduce(rec: dict) -> dict:
    """``layer_s`` (device time per layer kind, ``None`` = unscoped), ``label_s`` (per
    label on the operations' ``op_name`` paths, each operation under every label of its
    path: scopes nest, so labels overlap and do not add up to the busy time), ``kernel_s``
    (per kernel or custom-call name), ``idle_s`` (idle time per program span,
    ``unattributed`` under none), ``busy_s``, ``window_s``, ``steps`` and the heaviest
    unscoped operations."""
    devices = rec["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane with operations")
    op_names = rec["op_names"] or {}
    spans = sorted((name, s, s + d) for name, _, s, d in rec["spans"])
    n = len(devices)
    layer_s: dict = {}
    label_s: dict[str, float] = {}
    labels_of: dict[str, frozenset] = {}  # instruction -> the labels on its path
    kernel_s: dict[str, float] = {}
    idle_s: dict[str, float] = {}
    unscoped: dict[str, float] = {}
    busy = window = 0.0
    steps = 0
    for dev in devices:
        totals: dict[str, float] = {}
        for name, _, dur in dev["modules"]:
            totals[name] = totals.get(name, 0.0) + dur
        step_module = max(totals, key=totals.get)
        runs = sorted(s for name, s, _ in dev["modules"] if name == step_module)
        if len(runs) < 2:
            raise ValueError(f"{dev['name']}: {len(runs)} runs of {step_module!r}; need two or more")
        lo, hi = runs[0], runs[-1]
        clipped = []
        for name, start, dur in dev["ops"]:
            a, b = max(start, lo), min(start + dur, hi)
            if b <= a:
                continue
            clipped.append((a, b))
            if name.endswith(trace_lib._CONTAINERS):
                continue  # a loop's event spans its body's
            ins = instruction(name)
            layer = layer_of(ins, op_names.get(ins))
            layer_s[layer] = layer_s.get(layer, 0.0) + (b - a)
            if ins not in labels_of:
                labels_of[ins] = frozenset(_LABEL_RE.findall(op_names.get(ins) or ""))
            for label in labels_of[ins]:
                label_s[label] = label_s.get(label, 0.0) + (b - a)
            if layer is None:
                unscoped[name] = unscoped.get(name, 0.0) + (b - a)
            if name.endswith(" custom-call"):
                kernel_s[kernel(name)] = kernel_s.get(kernel(name), 0.0) + (b - a)
        merged = trace_lib._union(clipped)
        busy += sum(b - a for a, b in merged)
        window += hi - lo
        steps = len(runs) - 1
        edges = [lo, *[x for ab in merged for x in ab], hi]
        near = [sp for sp in spans if sp[2] > lo and sp[1] < hi]
        for i in range(0, len(edges), 2):
            if edges[i + 1] > edges[i]:
                for name, t in _innermost(near, edges[i], edges[i + 1]).items():
                    idle_s[name] = idle_s.get(name, 0.0) + t
    scale = 1e-9 / n
    return {
        "steps": steps, "busy_s": busy * scale, "window_s": window * scale,
        "layer_s": {k: v * scale for k, v in layer_s.items()},
        "label_s": {k: v * scale for k, v in label_s.items()},
        "kernel_s": {k: v * scale for k, v in kernel_s.items()},
        "idle_s": {k: v * scale for k, v in idle_s.items()},
        "unscoped_top": [[k, v * scale] for k, v in
                         sorted(unscoped.items(), key=lambda kv: -kv[1])[:8]],
        "span_names": sorted({name for name, _, _ in spans}),
        "has_scopes": rec["op_names"] is not None,
    }


def of(run: dict) -> dict | None:
    """The reduction of this run's trace, made once and kept on ``run``. ``None`` where
    there is nothing to read: a rehearsal (no device trace), or a program from before it
    wrote spans and ``step_scopes.json`` (neither is there: the metrics that read them
    are left out of the line). A program that writes one and not the other, or a span,
    scope or kernel a metric asks for and the trace lacks, is an error: a rename in the
    program stops the run instead of thinning its line."""
    if not run.get("trace"):
        return None
    if "_spans" not in run:
        trace_dir = run["run"].trace_dir
        rec = record(trace_lib.newest_xplane(trace_dir),
                     os.path.join(os.path.dirname(trace_dir), "step_scopes.json"))
        red = reduce(rec)
        has_spans = "train_step" in red["span_names"]
        if has_spans != red["has_scopes"]:
            raise RuntimeError(f"the program wrote spans: {has_spans}, step_scopes.json: "
                               f"{red['has_scopes']}; it writes both or (before PR 25) neither")
        if not has_spans:
            print("spans: the program writes no train_step span and no step_scopes.json "
                  "(a program from before PR 25): per-scope and per-span metrics left out",
                  flush=True)
            red = None
        else:
            _print(red)
        run["_spans"] = red
    return run["_spans"]


def _print(red: dict) -> None:
    per = 1e3 / red["steps"]
    layers = ", ".join(f"{k or 'unscoped'} {v * per:.3f}" for k, v in
                       sorted(red["layer_s"].items(), key=lambda kv: -kv[1]))
    idle = ", ".join(f"{k} {v * per:.3f}" for k, v in
                     sorted(red["idle_s"].items(), key=lambda kv: -kv[1]))
    print(f"device ms a step by layer kind: {layers} (sum "
          f"{sum(red['layer_s'].values()) * per:.3f}, busy {red['busy_s'] * per:.3f})", flush=True)
    print(f"device idle ms a step by innermost program span: {idle} (sum "
          f"{sum(red['idle_s'].values()) * per:.3f}, window - busy "
          f"{(red['window_s'] - red['busy_s']) * per:.3f})", flush=True)
    print("heaviest unscoped operations, ms a step: " + ", ".join(
        f"{k} {v * per:.3f}" for k, v in red["unscoped_top"]), flush=True)


def layer_ms(run: dict, *layers) -> float | None:
    """Device ms a traced step under the given layer kinds (``None``: unscoped)."""
    red = of(run)
    if red is None:
        return None
    missing = [k for k in layers if k not in red["layer_s"]]
    if missing:
        raise RuntimeError(f"no device operation under scope {missing} in the trace: the "
                           f"label moved (have {sorted(map(str, red['layer_s']))})")
    return 1e3 * sum(red["layer_s"][k] for k in layers) / red["steps"]


def scope_ms(run: dict, *labels) -> float | None:
    """Device ms a traced step of the operations whose ``op_name`` path carries the
    label, summed over ``labels`` as they are named in the program (``moe_gate``,
    ``moe_shared_experts``): a reader of its own for a scope that ``layer_ms`` folds into
    a layer kind. An operation under two of the labels counts under each."""
    red = of(run)
    if red is None:
        return None
    missing = [k for k in labels if k not in red["label_s"]]
    if missing:
        raise RuntimeError(f"no device operation under the label {missing} in the trace: "
                           f"the label moved (have {sorted(red['label_s'])})")
    return 1e3 * sum(red["label_s"][k] for k in labels) / red["steps"]


def idle_ms(run: dict, *names, required: tuple = ()) -> float | None:
    """Device idle ms a traced step whose innermost program span is one of ``names``.
    ``required`` spans have to be in the trace at all (idle under them or not)."""
    red = of(run)
    if red is None:
        return None
    missing = [k for k in required if k not in red["span_names"]]
    if missing:
        raise RuntimeError(f"no program span named {missing} in the trace: the span moved "
                           f"(have {red['span_names']})")
    return 1e3 * sum(red["idle_s"].get(k, 0.0) for k in names) / red["steps"]


def kernels_ms(run: dict, parts: tuple[str, ...]) -> float | None:
    """Device ms a traced step of the custom calls whose name holds one of ``parts`` (a
    backward kernel called under no scope reads ``transpose_jvp_<name>__``)."""
    red = of(run)
    if red is None:
        return None
    found = {k: v for k, v in red["kernel_s"].items() if any(p in k for p in parts)}
    if not found:
        raise RuntimeError(f"no custom call named {parts} in the trace: the kernel's "
                           f"name moved (have {sorted(red['kernel_s'])})")
    return 1e3 * sum(found.values()) / red["steps"]


if __name__ == "__main__":
    # python3 -m benchmarks.harness.spans <run directory> [record.json.gz]: what a traced
    # run's files reduce to and, with a second argument, the record a fixture is cut from
    import gzip
    import sys

    out_dir = sys.argv[1]
    rec = record(trace_lib.newest_xplane(os.path.join(out_dir, "trace")),
                 os.path.join(out_dir, "step_scopes.json"))
    _print(reduce(rec))
    if len(sys.argv) > 2:
        with gzip.open(sys.argv[2], "wt") as f:
            json.dump(rec, f, separators=(",", ":"))
