"""Jit'd public wrappers for the Pallas kernels.

The TPU is the *target*; on CPU (this container) every kernel runs in
``interpret=True`` mode, which executes the kernel body in Python for
correctness validation against :mod:`repro.kernels.ref`.  These wrappers
always run the Pallas kernels; a caller that wants a pure-jnp path imports
it from :mod:`repro.models.layers` or :mod:`repro.kernels.ref` instead.
"""
from __future__ import annotations

import functools

import jax

from .flash_attention import flash_attention as _flash
from .paged_kv_gather import paged_decode_attention as _paged
from .wkv6 import wkv6 as _wkv6

__all__ = ["flash_attention", "paged_decode_attention", "wkv6", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, sliding_window=None,
                    block_q: int = 256, block_k: int = 256):
    """(B, Hq, S, D) head-major flash attention."""
    return _flash(q, k, v, causal=causal, sliding_window=sliding_window,
                  block_q=block_q, block_k=block_k, interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("n_buffers",))
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           n_buffers: int = 2):
    """Decode attention over a slow-tier page store with DMA prefetch."""
    return _paged(q, k_pages, v_pages, block_tables, lengths,
                  n_buffers=n_buffers, interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(r, k, v, log_w, u, chunk: int = 64):
    """RWKV-6 linear recurrence, chunk-streamed."""
    return _wkv6(r, k, v, log_w, u, chunk=chunk, interpret=not on_tpu())
