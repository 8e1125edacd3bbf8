#!/usr/bin/env python
"""Generate docs/model-coverage.md from the model registry (single source of truth).

The supported-architecture story lives in three structures —
``models/registry.py:MODEL_REGISTRY`` (explicit arch -> family mappings),
``models/structural.py:_ARCH_DELTAS`` (unregistered architectures verified to
alias onto the llama lineage with config-invisible deltas), and
``models/structural.py:_DENYLIST`` (architectures pinned out with a reason).
Hand-maintained docs drift the moment a family lands; this tool renders the
three structures into ``docs/model-coverage.md`` and the freshness test
(tests/unit/test_model_coverage_doc.py) fails CI when the committed doc no
longer matches a regeneration.

The registry modules are loaded straight from their files (no
``automodel_tpu`` package import), so generation needs neither jax nor any
model code.

Usage:
    python tools/gen_model_coverage.py            # rewrite docs/model-coverage.md
    python tools/gen_model_coverage.py --check    # exit 1 if the doc is stale
"""

from __future__ import annotations

import argparse
import importlib.util
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
DOC = REPO / "docs" / "model-coverage.md"

_HEADER = """\
# Model coverage

<!-- Generated by tools/gen_model_coverage.py from models/registry.py and
     models/structural.py. Do not edit by hand: regenerate with
     `python tools/gen_model_coverage.py`. CI keeps it fresh via
     tests/unit/test_model_coverage_doc.py. -->
"""


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _family(target: str) -> tuple[str, str]:
    """("models/llama", "LlamaForCausalLM") from a registry import string."""
    mod, cls = target.split(":")
    return mod.removeprefix("automodel_tpu.").replace(".", "/").removesuffix("/model"), cls


def _render_delta(delta) -> str:
    if callable(delta):
        return ("config-dependent delta (see `_ARCH_DELTAS` in "
                "`models/structural.py`)")
    return ", ".join(f"`{k}={v!r}`" for k, v in sorted(delta.items()))


def render() -> str:
    registry = _load(REPO / "automodel_tpu" / "models" / "registry.py",
                     "_coverage_registry")
    structural = _load(REPO / "automodel_tpu" / "models" / "structural.py",
                       "_coverage_structural")
    reg: dict[str, str] = registry.MODEL_REGISTRY
    deltas = structural._ARCH_DELTAS
    denylist = structural._DENYLIST

    families = sorted({_family(t)[0] for t in reg.values()})
    lines = [_HEADER]
    lines.append(
        f"**{len(reg)} registered architectures** across {len(families)} "
        f"family modules, plus {len(deltas)} architectures supported through "
        "config-level structural aliasing. Architectures the registry does "
        "not know are auto-checked against the llama lineage field-by-field "
        "(`models/structural.py`); a config that genuinely diverges raises "
        "`StructuralDivergence` naming the field rather than loading with "
        "wrong math.\n")

    lines.append("## Registered architectures\n")
    lines.append("The HF `config.json` `architectures[0]` name resolves "
                 "through `models/registry.py:MODEL_REGISTRY`. Multiple "
                 "architectures mapping to one family share its "
                 "implementation (config-level variants).\n")
    lines.append("| Architecture | Family module | Class |")
    lines.append("| --- | --- | --- |")
    for arch in sorted(reg):
        fam, cls = _family(reg[arch])
        lines.append(f"| `{arch}` | `{fam}` | `{cls}` |")
    lines.append("")

    lines.append("## Structurally aliased architectures\n")
    lines.append(
        "Unregistered `*ForCausalLM` architectures whose configs pass the "
        "llama-lineage field check load via `resolve_llama_delta`. These "
        "entries carry *code-level* deltas that the config cannot express, "
        "verified by the logits-parity suite "
        "(`tests/unit/test_structural_alias.py`):\n")
    lines.append("| Architecture | Delta onto the llama lineage |")
    lines.append("| --- | --- |")
    for arch in sorted(deltas):
        lines.append(f"| `{arch}` | {_render_delta(deltas[arch])} |")
    lines.append("")

    lines.append("## Denylisted architectures\n")
    lines.append(
        "Configs that LOOK llama-shaped but whose transformer block differs "
        "in code — field inspection cannot see code, so these are pinned out "
        "by hand and fail fast with the reason:\n")
    lines.append("| Architecture | Reason |")
    lines.append("| --- | --- |")
    for arch in sorted(denylist):
        lines.append(f"| `{arch}` | {denylist[arch]} |")
    lines.append("")

    import json

    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    lines.append("## Measured on the chip at published widths\n")
    lines.append(
        "Registered is not measured. These configurations run through the "
        "recipe at their published widths in `BENCHMARK.json`'s cells, each "
        "checked against a plain float32 reference (`benchmarks/reference/`); "
        "PERF.md says how each was cut to one chip. A row proves the TRAIN path on "
        "ONE chip (forward, backward, optimizer step; where the configuration holds "
        "a share of its experts, that share without its exchange). It proves "
        "neither decoding through the cache nor a mesh of several chips, which no "
        "cell runs:\n")
    lines.append("| Configuration | Architecture | Cells |")
    lines.append("| --- | --- | --- |")
    for config in bench["configs"]:
        arch = json.loads((REPO / config["file"]).read_text())["architectures"][0]
        cells = ", ".join(f"`{w['name']}`" for w in bench["workloads"]
                          if w["config"] == config["name"])
        lines.append(f"| `{config['name']}` | `{arch}` | {cells} |")
    lines.append("")

    lines.append(
        "Day-0 support for a new config-level variant of a registered "
        "family: `automodel_tpu.models.registry.register_model(arch, "
        "\"module:Class\")`. Genuinely new blocks become families under "
        "`automodel_tpu/models/`.")
    return "\n".join(lines) + "\n"


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--check", action="store_true",
                        help="exit 1 if docs/model-coverage.md is stale")
    args = parser.parse_args(argv)
    text = render()
    rel = DOC.relative_to(REPO) if DOC.is_relative_to(REPO) else DOC
    if args.check:
        current = DOC.read_text() if DOC.exists() else ""
        if current != text:
            print(f"{rel} is stale — regenerate with "
                  "`python tools/gen_model_coverage.py`", file=sys.stderr)
            return 1
        print(f"{rel} is fresh")
        return 0
    DOC.write_text(text)
    print(f"wrote {rel}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
