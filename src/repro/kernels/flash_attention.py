"""Blocked flash attention as a Pallas TPU kernel.

TPU-native tiling: q is blocked (BQ x D) in VMEM, the kv loop is the
innermost ('arbitrary') grid dimension so K/V blocks stream HBM -> VMEM
through the automatic Pallas pipeline -- the hardware analogue of the
paper's prefetch-and-yield: block i+1 is being DMA'd while block i is on
the MXU. Online softmax state (m, l, acc) lives in VMEM scratch across kv
iterations. Causal/sliding-window blocks that are fully masked are skipped
via the grid index map (work elision, not masking).

Layout notes (MXU/VPU alignment): BQ and BK are multiples of 128 when the
sequence allows; D (head_dim) 64/128/256 are all lane-aligned. Grouped
query heads are folded into the q-block rows so GQA does not replicate KV.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, block_q: int, block_k: int, scale: float,
            seq_len: int, sliding_window: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale     # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)             # (BK, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (BQ, BK)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < seq_len
    if causal:
        mask &= q_pos >= k_pos
    if sliding_window:
        mask &= q_pos - k_pos < sliding_window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ()))
    )

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,              # (B, Hq, S, D)  -- head-major layout
    k: jnp.ndarray,              # (B, Hkv, S, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    sliding_window: int | None = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (B, Hq, S, D). Grouped heads: Hq % Hkv == 0."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(S, block_k)
    scale = 1.0 / math.sqrt(D)

    grid = (B, Hq, nq, nk)

    kernel = functools.partial(
        _kernel, causal=causal, block_q=block_q, block_k=block_k,
        scale=scale, seq_len=S, sliding_window=sliding_window or 0,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),      # m
            pltpu.VMEM((block_q, 1), jnp.float32),      # l
            pltpu.VMEM((block_q, D), jnp.float32),      # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
