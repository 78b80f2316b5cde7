"""The benchmark's one generator: operands from a traffic file and a seed.

A traffic file lists its operands by name, shape and distribution; the
configuration gives the tier and the limb dtype (f32: every tier runs over
f32 limbs on the chip).  Every operand is made on the device in one jitted
call, with signal in every limb, and the same seed gives the same
operands.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DISTRIBUTIONS = ("normal", "uniform")
# the tier below each tier: the controls' precision, the step that would
# tempt a later change
LOWER = {"td": "dd", "qd": "td"}


def key_from_seed(seed: int):
    """A PRNG key from any whole number, however wide (seeds may pass 32
    signed bits)."""
    words = np.random.SeedSequence(seed % 2 ** 64).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words))


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """A host generator for sampling (which calls and entries are checked),
    independent of the operands' stream."""
    return np.random.default_rng([seed % 2 ** 64, stream])


def half_ulp(x):
    """ulp(x) / 2 for f32 x, exactly: the power of two of x's exponent
    field, times 2^-24 (0 for zero and subnormal x)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & 0x7F800000
    return jax.lax.bitcast_convert_type(bits, jnp.float32) * 2.0 ** -24


def random_tier(key, shape, precision: str, dtype, dist: str):
    """A multi-limb operand built limb by limb: the top limb is the draw,
    and each lower limb a uniform draw in [-1, 1) times half an ulp of the
    limb above it, so the limbs never overlap and each carries signal.
    The limbs are placed as they are (``mp.from_limbs``), without the
    program's own arithmetic."""
    from repro.core import mp

    if np.dtype(dtype) != np.float32:
        raise TypeError(f"the benchmark makes f32 limbs, not {dtype}")
    keys = jax.random.split(key, mp.PRECISIONS[precision])
    draw = {"normal": jax.random.normal, "uniform": jax.random.uniform}[dist]
    limbs = [draw(keys[0], shape, jnp.float32)]
    for k in keys[1:]:
        u = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0)
        limbs.append(u * half_ulp(limbs[-1]))
    return mp.from_limbs(limbs)


def make(specs, precision: str, dtype, seed: int) -> dict:
    """{name: operand} for the traffic file's ``operands`` list."""
    for s in specs:
        if s["dist"] not in DISTRIBUTIONS:
            raise ValueError(f"operand {s['name']}: unknown distribution "
                             f"{s['dist']!r}; one of {DISTRIBUTIONS}")

    def build(key):
        return {s["name"]: random_tier(jax.random.fold_in(key, i),
                                       tuple(s["shape"]), precision, dtype,
                                       s["dist"])
                for i, s in enumerate(specs)}

    return jax.block_until_ready(jax.jit(build)(key_from_seed(seed)))
