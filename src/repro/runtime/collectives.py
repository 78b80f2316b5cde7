"""Distributed-optimization collectives.

compensated_psum — the paper's high-precision accumulator, distributed.
Cross-replica gradient reduction in f32 loses low bits as the replica count
grows (and is order-dependent).  We split each operand into error-free
mantissa slices (efts.split, 12 bits each): the top-slice psum is
EXACT for up to 2^(24-2*12)=... practically the top slice sums exactly for
thousands of replicas (12-bit values, f32 accumulator), and each further
slice extends precision by 12 bits.  Recombination uses two_sum.  With
slices=2 this is df32-grade ("binary64-ish") reduction; slices=4 exceeds
f64.  This is the distributed cousin of the paper's binary128 MAC.

int8 all-reduce with error feedback — bandwidth-oriented gradient
compression: per-block int8 quantization before the reduce; the
quantization residual is fed back into the next step's gradient so the
error stays bounded instead of accumulating (Seide et al. / EF-SGD).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.efts import quick_two_sum, split, two_sum

__all__ = ["compensated_psum", "int8_psum_ef", "EFState"]

# largest |x| whose Veltkamp splitter product x * (2^s + 1) stays finite
_SPLIT_MAX = {jnp.dtype(jnp.float32): 2.0 ** 115,
              jnp.dtype(jnp.float64): 2.0 ** 995}


def compensated_psum(x, axis_name: str, slices: int = 2):
    """High-precision psum over a mesh axis via error-free slice reduction."""
    # beyond the split's range (its splitter product would overflow) a
    # value goes whole into the top slice: a plain psum, but finite
    big = jnp.abs(x) > _SPLIT_MAX[jnp.dtype(x.dtype)]
    residual = x
    parts = []
    for _ in range(max(1, slices - 1)):
        hi, lo = split(jnp.where(big, 0, residual))
        hi = jnp.where(big, residual, hi)
        residual = jnp.where(big, 0, lo)
        parts.append(jax.lax.psum(hi, axis_name))
    parts.append(jax.lax.psum(residual, axis_name))
    # recombine with exact two_sum chain (descending magnitude)
    s = parts[0]
    err = jnp.zeros_like(s)
    for p in parts[1:]:
        s, e = two_sum(s, p)
        err = err + e
    out, _ = quick_two_sum(s, err)
    return out


class EFState(NamedTuple):
    residual: jnp.ndarray  # carried quantization error (error feedback)


def _q8(x, block=256):
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    b = jnp.pad(flat, (0, pad)).reshape(-1, block)
    scale = jnp.maximum(jnp.max(jnp.abs(b), axis=1, keepdims=True) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(b / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dq8(q, scale, shape):
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    n = 1
    for s in shape:
        n *= s
    return flat[:n].reshape(shape)


def int8_psum_ef(g, ef: EFState, axis_name: str):
    """int8-compressed psum with error feedback.

    Returns (reduced_fp32, new_ef).  The int8 payload is what would cross
    the wire (8x compression vs f32); the psum itself runs on the
    dequantized tensor because XLA collectives do not expose int8 ring
    stages — the quantization error behaviour (the part that affects
    convergence) is faithfully modeled, the bandwidth saving is structural.
    """
    comp = g + ef.residual
    q, scale = _q8(comp)
    deq = _dq8(q, scale, g.shape)
    new_ef = EFState(comp - deq)
    return jax.lax.psum(deq, axis_name), new_ef
