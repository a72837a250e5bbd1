"""TPU Pallas blockwise RG-LRU linear recurrence:  h_t = a_t * h_{t-1} + b_t.

The gates/decay (a, b) are cheap einsums computed outside; the kernel owns the
sequential scan, tiled (block_s × width) per grid step with the carry h in
VMEM scratch persisting across the sequential minor grid dim.  Each in-block
step is a (width,)-wide VPU op — the TPU-native replacement for the
associative-scan tree the XLA path uses (lower peak memory, zero re-layout).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, o_ref, h_ref, *, block_s):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):                                # h: (1, bw) f32
        h = a_ref[0, pl.ds(t, 1), :] * h + b_ref[0, pl.ds(t, 1), :]
        o_ref[0, pl.ds(t, 1), :] = h
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_s, step, h_ref[...])


def rglru_scan(a, b, *, block_s=256, interpret=False):
    """a, b: (B, S, W) f32 -> h sequence (B, S, W) f32.

    The recurrence is elementwise in W, so W is tiled too (by 512 when that
    divides W, else whole): three double-buffered (block_s, 512) f32 blocks
    stay far inside the scoped VMEM limit at any width.
    """
    B, S, W = a.shape
    bs = min(block_s, S)
    bw = 512 if W % 512 == 0 else W
    ns = -(-S // bs)
    pad = ns * bs - S
    ap = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
    bp = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
    spec = pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi))
    out = pl.pallas_call(
        functools.partial(_rglru_kernel, block_s=bs),
        grid=(B, W // bw, ns),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, ns * bs, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret,
        name="rglru_scan",
    )(ap, bp)
    return out[:, :S]
