"""The benchmark's own weights: every leaf from `--seed` in one jitted
call on the device, in the type the cell runs in. The program and the
plain reference are handed the same arrays; neither makes its own."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_STD = 0.02


def seed_key(seed: int):
    """A key from any whole number up to 2**63: the low 31 bits seed it
    and the rest is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (2 ** 31)),
                              seed // (2 ** 31))


def make(shapes: dict, seed: int, dtype=jnp.float32) -> dict:
    """`shapes` maps a leaf's name to (shape, kind). A `weight` or
    `bias` is N(0, 0.02); a `gain` is 1 + N(0, 0.02), so that a norm's
    scale and shift are not the identity the check could not see."""
    names = sorted(shapes)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = shapes[name]
            x = _STD * jax.random.normal(jax.random.fold_in(key, i),
                                         tuple(shape), jnp.float32)
            if kind == "gain":
                x = 1.0 + x
            out[name] = x.astype(dtype)
        return out

    return jax.jit(build)(seed_key(seed))
