"""Seeded weights, made by the benchmark on the device in one jitted call.

Both sides start from these: the program is handed them (through the family's
adapter, which only renames and stacks), the reference makes its own copy from the
same seed. ``normal(0, initializer_range)`` matrices, unit norm scales and zero buffers
(``init`` of the reference's ``block_shapes``), in the type the cell trains in.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp


_CONSTANT = {"ones": jnp.ones, "zeros": jnp.zeros}  # every other init is drawn: ``normal``


def seed_key(seed: int):
    """A key from any whole number: more than 32 signed bits are folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def maker(reference, m: dict, dtype_name: str):
    """The traceable function ``key -> {block: {leaf: array}}`` for the shapes of
    ``reference`` (the cell's ``benchmarks/reference/<name>.py``). A leaf's key is folded
    from its place in the order of blocks and leaves, whatever its init."""
    shapes = reference.block_shapes(m)
    std = float(m.get("initializer_range", 0.02))
    dtype = jnp.dtype(dtype_name)

    def make(key):
        out, index = {}, 0
        for block, leaves in shapes.items():
            out[block] = {}
            for name, (shape, init) in leaves.items():
                index += 1
                if init in _CONSTANT:
                    out[block][name] = _CONSTANT[init](shape, dtype)
                elif init == "normal":
                    k = jax.random.fold_in(key, index)
                    out[block][name] = (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
                else:
                    raise ValueError(f"{block}.{name}: init {init!r} is not normal, ones or zeros")
        return out

    return make


@functools.lru_cache(maxsize=None)
def _jitted_maker(reference, m_key: str, dtype_name: str):
    return jax.jit(maker(reference, json.loads(m_key), dtype_name))


def make_blocks(reference, m: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """``{block: {leaf: array}}`` in the reference's layout."""
    return _jitted_maker(reference, json.dumps(m, sort_keys=True), dtype)(seed_key(seed))


def stack_layers(blocks: dict, groups: dict[str, list[int]]) -> dict:
    """``{"embed", "final_norm", "lm_head", "<group>.<leaf>" (layers of the group, ...)}``:
    each group of the reference's ``layer_groups`` stacked on a leading axis, as the
    program keeps its stacks. Traceable."""
    flat = {"embed": blocks["embed"]["embed"], **blocks["head"]}
    for group, indices in groups.items():
        layers = [blocks[f"layer_{i}"] for i in indices]
        for name in layers[0]:
            flat[f"{group}.{name}"] = jnp.stack([layer[name] for layer in layers])
    return flat
