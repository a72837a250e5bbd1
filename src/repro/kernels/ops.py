"""Jit'd dispatch wrappers: the Pallas kernels or their pure-jnp oracles.

Models call these; ``use_pallas`` is RunPolicy-driven.  With ``use_pallas``
the kernel is compiled by Mosaic for the TPU.  ``interpret=True`` runs it in
the Pallas interpreter instead; only tests ask for that.  There is no
fallback: on a backend that is not a TPU, lowering a kernel without
``interpret=True`` raises.
"""
from __future__ import annotations

import functools

import jax

from . import ref
from .decode_attention import flash_decode as _flash_decode
from .flash_attention import flash_attention as _flash_attention
from .rglru_scan import rglru_scan as _rglru_scan
from .rwkv6_kernel import rwkv6_wkv as _rwkv6_wkv


@functools.partial(jax.jit, static_argnames=("window", "use_pallas",
                                             "block_q", "block_k",
                                             "interpret"))
def attention(q, k, v, *, window=None, use_pallas=True,
              block_q=None, block_k=None, interpret=False):
    """Causal attention; the kernel's blocks, left ``None``, are chosen from
    the shapes (``flash_attention.choose_blocks``)."""
    if use_pallas:
        return _flash_attention(q, k, v, window, 0, block_q, block_k,
                                interpret)
    return ref.flash_attention_ref(q, k, v, window=window)


@functools.partial(jax.jit, static_argnames=("window", "use_pallas",
                                             "block_k", "interpret"))
def decode_attention(q, k, v, pos, qpos, *, window=None, use_pallas=True,
                     block_k=512, interpret=False):
    if use_pallas:
        return _flash_decode(q, k, v, pos, qpos, window=window,
                             block_k=block_k, interpret=interpret)
    return ref.flash_decode_ref(q, k, v, pos, qpos, window=window)


@functools.partial(jax.jit, static_argnames=("use_pallas", "block_s",
                                             "interpret"))
def rglru(a, b, *, use_pallas=True, block_s=256, interpret=False):
    if use_pallas:
        return _rglru_scan(a, b, block_s=block_s, interpret=interpret)
    return ref.rglru_scan_ref(a, b)


@functools.partial(jax.jit, static_argnames=("use_pallas", "chunk",
                                             "interpret"))
def rwkv6(r, k, v, w_log, u, *, use_pallas=True, chunk=64, interpret=False):
    if use_pallas:
        return _rwkv6_wkv(r, k, v, w_log, u, chunk=chunk,
                          interpret=interpret)
    return ref.rwkv6_wkv_ref(r, k, v, w_log, u)
