"""Seeded weights, made by the benchmark on the device in one jitted call.

Both sides start from these: the program is handed them (through the family's
adapter, which only renames and stacks), the reference makes its own copy from the
same seed. ``normal(0, initializer_range)`` matrices and unit norm scales, in the
type the cell trains in.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmarks.reference import decoder


def seed_key(seed: int):
    """A key from any whole number: more than 32 signed bits are folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def maker(m: dict, dtype_name: str):
    """The traceable function ``key -> {block: {leaf: array}}``."""
    shapes = decoder.block_shapes(m)
    std = float(m.get("initializer_range", 0.02))
    dtype = jnp.dtype(dtype_name)

    def make(key):
        out, index = {}, 0
        for block, leaves in shapes.items():
            out[block] = {}
            for name, (shape, init) in leaves.items():
                index += 1
                if init == "ones":
                    out[block][name] = jnp.ones(shape, dtype)
                else:
                    k = jax.random.fold_in(key, index)
                    out[block][name] = (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
        return out

    return make


@functools.lru_cache(maxsize=None)
def _jitted_maker(m_key: str, dtype_name: str):
    return jax.jit(maker(json.loads(m_key), dtype_name))


def make_blocks(m: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """``{block: {leaf: array}}`` in the reference's layout."""
    return _jitted_maker(json.dumps(m, sort_keys=True), dtype)(seed_key(seed))


def stack_layers(blocks: dict) -> dict:
    """``{"embed", "layers.<leaf>" (L, ...), "final_norm", "lm_head"}``: the layers
    stacked on a leading axis. Traceable."""
    layers = [blocks[b] for b in sorted((b for b in blocks if b.startswith("layer_")),
                                        key=lambda b: int(b.split("_")[1]))]
    flat = {"embed": blocks["embed"]["embed"], **blocks["head"]}
    for name in layers[0]:
        flat["layers." + name] = jnp.stack([layer[name] for layer in layers])
    return flat
