"""Which implementation a Pallas call site runs: decided here, and recorded.

Every place that can swap a Pallas kernel for plain XLA (or run the kernel
through the interpreter) asks the two functions of this module instead of
reading ``jax.default_backend()`` itself:

- :func:`interpret_mode` — "would a ``pallas_call`` here be interpreted?"
- :func:`kernel_usable` — "can the requested kernel run here, and if not, why?"

Each distinct answer is logged once, at trace time, and kept in a process-wide
table that the recipe writes into the run header (``kernels: {attention, loss,
experts, interpret, reasons}``), so a run that asked for ``attention: flash``
and got the f32 einsum says so in its own ``training.jsonl``.
``chip_smoke.py`` reads that header through :func:`require_compiled`.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Sequence

import jax

__all__ = [
    "KernelResolutionError", "check_manual_region", "interpret_mode", "kernel_usable",
    "manual_axes", "note",
    "out_struct", "require_compiled", "reset", "snapshot",
]

logger = logging.getLogger(__name__)

_lock = threading.Lock()
# (kernel, resolved, interpret, reason) -> times that answer was given
_seen: dict[tuple[str, str, bool, str | None], int] = {}


class KernelResolutionError(RuntimeError):
    """A kernel was asked for where it cannot run (or did not run compiled)."""


def interpret_mode() -> bool:
    """Pallas kernels go through the interpreter wherever the default backend
    is not a TPU: same kernel logic, no Mosaic, no speed."""
    return jax.default_backend() != "tpu"


def note(kernel: str, resolved: str, *, interpret: bool = False,
         reason: str | None = None) -> None:
    """Record that ``kernel`` resolved to ``resolved``; log the first time."""
    key = (kernel, resolved, bool(interpret), reason)
    with _lock:
        first = key not in _seen
        _seen[key] = _seen.get(key, 0) + 1
    if first:
        logger.info(
            "kernel %s -> %s%s%s", kernel, resolved,
            " (interpret mode)" if interpret else "",
            f": {reason}" if reason else "",
        )


def kernel_usable(
    kernel: str,
    *,
    requested: str,
    fallback: str,
    needs: Sequence[tuple[bool, str]] = (),
    interpret: bool | None = None,
) -> bool:
    """Can ``requested`` run at this call site? Records the answer either way.

    ``needs`` lists ``(holds, what it means when it does not)`` pairs for the
    shapes/arguments the kernel supports. ``interpret=None`` is the kernel
    that only counts compiled — off the TPU it is unusable and the site takes
    ``fallback``; a bool is the site that also runs interpreted (the value
    says whether it will be).
    """
    why = [msg for ok, msg in needs if not ok]
    if interpret is None:
        interpret = False
        if interpret_mode():
            why.insert(0, f"default backend is {jax.default_backend()}, not tpu")
    if why:
        note(kernel, fallback, reason=f"{requested} unusable: " + "; ".join(why))
        return False
    note(kernel, requested, interpret=interpret)
    return True


def snapshot() -> dict[str, Any]:
    """Run-header view: per kernel what it resolved to (``a+b`` when call sites
    disagreed), whether any ran interpreted, and every reason given."""
    with _lock:
        seen = dict(_seen)
    out: dict[str, Any] = {"interpret": any(k[2] for k in seen)}
    reasons: dict[str, list[str]] = {}
    counts: dict[str, int] = {}
    for (kernel, resolved, _, reason), n in sorted(seen.items(), key=str):
        names = out.setdefault(kernel, [])
        if resolved not in names:
            names.append(resolved)
        if reason:
            reasons.setdefault(kernel, []).append(f"{resolved}: {reason}")
            counts[kernel] = counts.get(kernel, 0) + n
    for kernel in [k for k in out if k != "interpret"]:
        out[kernel] = "+".join(out[kernel])
    out["reasons"] = reasons
    out["fallback_traces"] = counts
    return out


def require_compiled(kernels: dict[str, Any], **expected: str) -> None:
    """Raise unless ``kernels`` (a :func:`snapshot`) shows exactly the expected
    implementation per kernel, compiled, with no fallback taken anywhere."""
    problems = []
    if kernels.get("interpret"):
        problems.append("a kernel ran in interpret mode")
    for kernel, want in expected.items():
        got = kernels.get(kernel)
        if got != want:
            why = "; ".join(kernels.get("reasons", {}).get(kernel, [])) or "never traced"
            problems.append(f"{kernel}: wanted {want}, got {got} ({why})")
    if problems:
        raise KernelResolutionError("; ".join(problems))


def manual_axes(mesh, *axes: str) -> frozenset[str]:
    """``axis_names`` for a ``shard_map`` that splits its work over ``axes``:
    those, plus every mesh axis of size 1.

    JAX lowers a Mosaic kernel only inside a region that is manual over EVERY
    mesh axis; an axis of size 1 that was left to GSPMD counts as missing
    (``jax._src.tpu_custom_call._tpu_custom_call_lowering``, JAX 0.9.0; a
    one-device mesh with ``axis_names={"ep"}`` is refused too). Being manual
    over an axis nothing is split over changes no value, so a mesh whose other
    axes are all 1 — ``ep: 4`` or ``cp: 4`` on a four-chip host, any one-chip
    mesh — gets a fully manual region and keeps its compiled kernels."""
    return frozenset(axes) | {a for a in mesh.axis_names if mesh.shape[a] == 1}


def check_manual_region(kernel: str) -> None:
    """Raise, by name, where JAX would refuse to lower a compiled kernel.

    JAX lowers a Mosaic kernel for one device, or inside a region that is
    manual over EVERY mesh axis (see :func:`manual_axes`). A region that leaves
    an axis of size > 1 to GSPMD (``ep`` or ``cp`` beside ``dp_shard``, a
    pipeline stage beside anything) and a bare call under a multi-device mesh
    are refused at lowering with "Mosaic kernels cannot be automatically
    partitioned"; say it here, with the axes. What this can see is the mesh
    ``jax.sharding.set_mesh`` or an enclosing ``shard_map`` set; a bare call
    under a legacy ``with mesh:`` gets JAX's own message.
    """
    am = jax.sharding.get_abstract_mesh()
    manual = set(am.manual_axes)
    if am.empty or manual == set(am.axis_names) or (not manual and am.size == 1):
        return
    free = {a: am.shape[a] for a in am.axis_names if a not in manual}
    raise KernelResolutionError(
        f"{kernel}: a compiled Pallas kernel was reached "
        + (f"inside a region that is manual over {tuple(am.manual_axes)} while "
           if manual else "outside any manual region while ")
        + f"GSPMD still splits {free}; no Mosaic kernel is partitioned "
        "automatically. Give those axes size 1, route the call through a "
        "shard_map over them (ops.attention.sharded_attention, which needs the "
        "call site's sharding rules) or ask for the XLA implementation."
    )


def reset() -> None:
    """Forget every recorded resolution (tests; one process, several runs)."""
    with _lock:
        _seen.clear()


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """``pallas_call`` out-shape that varies over the mesh axes its operands
    vary over. Inside a ``shard_map`` with ``check_vma`` a kernel's outputs
    cannot infer this; outside one the set is empty and nothing changes."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in operands if a is not None))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
