"""RWKV-6 WKV recurrence as a Pallas TPU kernel.

Sequential over time (the recurrence is inherently serial), parallel over
(batch x head). Time is split into chunks that stream through VMEM via the
automatic pipeline (the 'arbitrary' innermost grid dimension); the (D x D)
matrix state persists in VMEM scratch across chunk iterations. Inside a
chunk the per-step update is VPU work: an outer product k v^T, a diagonal
decay scale, and a vector-matrix read-out r.S.

This is the TPU adaptation of the fla/RWKV CUDA kernels: where the GPU
version assigns a thread per channel and loops t in registers, the TPU
version assigns a grid cell per (b, h) and keeps the whole state tile
resident in VMEM -- same dataflow, memory-hierarchy-appropriate tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv6"]


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_ref, *,
            chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u_row = u_ref[0].astype(jnp.float32)             # (D,)

    def step(j, state):
        rt = r_ref[0, j, 0].astype(jnp.float32)      # (D,)
        kt = k_ref[0, j, 0].astype(jnp.float32)
        vt = v_ref[0, j, 0].astype(jnp.float32)
        wt = jnp.exp(lw_ref[0, j, 0].astype(jnp.float32))
        kv = kt[:, None] * vt[None, :]               # (D, D) outer product
        out = jnp.einsum(
            "d,de->e", rt, state + u_row[:, None] * kv,
            preferred_element_type=jnp.float32,
        )
        o_ref[0, j, 0] = out.astype(o_ref.dtype)
        return state * wt[:, None] + kv

    state = jax.lax.fori_loop(0, chunk, step, state_ref[...])
    state_ref[...] = state


def wkv6(
    r: jnp.ndarray,              # (B, S, H, D)
    k: jnp.ndarray,
    v: jnp.ndarray,
    log_w: jnp.ndarray,          # (B, S, H, D) <= 0
    u: jnp.ndarray,              # (H, D)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, H, D = r.shape
    chunk = min(chunk, S)
    nc = pl.cdiv(S, chunk)
    assert S % chunk == 0, "pad sequence to a chunk multiple"

    kernel = functools.partial(_kernel, chunk=chunk)
    spec = lambda b, h, c: (b, c, h, 0)
    blk = (1, chunk, 1, D)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec(blk, spec),
            pl.BlockSpec(blk, spec),
            pl.BlockSpec(blk, spec),
            pl.BlockSpec(blk, spec),
            pl.BlockSpec((1, D), lambda b, h, c: (h, 0)),
        ],
        out_specs=pl.BlockSpec(blk, spec),
        out_shape=jax.ShapeDtypeStruct((B, S, H, D), r.dtype),
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, log_w, u)
