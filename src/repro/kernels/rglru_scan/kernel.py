"""Pallas TPU kernel for the RG-LRU linear recurrence.

Computes h_t = a_t * h_{t-1} + b_t over the time axis for [B, S, W]
gate/input tensors (a, b precomputed by the surrounding block — the
matmuls stay on the MXU in XLA; the kernel owns the sequential hot
loop, which XLA otherwise lowers to an O(log S) associative scan with
S*log(S) HBM traffic).

Tiling: grid (B, num_W_blocks, num_S_blocks); the time axis is the
minormost (sequential) grid dim, so the carry h [bw] lives in VMEM
scratch across time blocks.  Within a block a fori_loop steps through
``block_s`` time steps of [bw]-wide vector ops — pure VPU work on lanes,
W-blocked to the 128-lane register width.  Each step loads its row from
the refs (``a_ref[0, t, :]``): Mosaic lowers a dynamic row index on a ref,
but not a dynamic slice of a value already loaded.  ``h0`` enters as
[B, 1, W] so its (1, 1, bw) block spans the array's full second-minor
dim, which the TPU tiling rule accepts for any B.

Per-step VMEM: a, b tiles (2 * bs * bw f32) + carry (bw f32): with
bs=256, bw=512 that is ~1 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_S = 256
DEFAULT_BLOCK_W = 512


def _scan_kernel(a_ref, b_ref, h0_ref, o_ref, carry_ref, *, block_s: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        carry_ref[...] = h0_ref[0, 0]

    def step(t, h):
        h = a_ref[0, t, :] * h + b_ref[0, t, :]
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_s, step, carry_ref[...])
    carry_ref[...] = h


def rglru_scan(a, b, h0=None, *, block_s: int = DEFAULT_BLOCK_S,
               block_w: int = DEFAULT_BLOCK_W, interpret: bool = False):
    """a, b [B, S, W] (f32 gates/inputs); h0 [B, W] or None.

    Returns (h [B, S, W], h_last [B, W]).
    """
    B, S, W = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    block_s = min(block_s, S)
    block_w = min(block_w, W)
    ns = pl.cdiv(S, block_s)
    nw = pl.cdiv(W, block_w)
    pad_s = ns * block_s - S
    pad_w = nw * block_w - W
    if pad_s or pad_w:
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_w)))
        b = jnp.pad(b, ((0, 0), (0, pad_s), (0, pad_w)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_w)))

    kernel = functools.partial(_scan_kernel, block_s=block_s)
    out = pl.pallas_call(
        kernel,
        grid=(B, nw, ns),
        in_specs=[
            pl.BlockSpec((1, block_s, block_w),
                         lambda ib, iw, it: (ib, it, iw)),
            pl.BlockSpec((1, block_s, block_w),
                         lambda ib, iw, it: (ib, it, iw)),
            pl.BlockSpec((1, 1, block_w), lambda ib, iw, it: (ib, 0, iw)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w),
                               lambda ib, iw, it: (ib, it, iw)),
        out_shape=jax.ShapeDtypeStruct((B, ns * block_s, nw * block_w),
                                       a.dtype),
        scratch_shapes=[pltpu.VMEM((block_w,), jnp.float32)],
        interpret=interpret,
    )(a, b, h0[:, None, :])
    h = out[:, :S, :W]
    return h, h[:, -1]
