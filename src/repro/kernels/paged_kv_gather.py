"""Paged decode attention with an explicit double-buffered DMA prefetch
pipeline -- the paper's technique, TPU-native.

The KV page store lives in a *slow tier* (HBM here; host memory on a real
deployment) and is never blocked by the automatic Pallas pipeline: pages
are pulled on demand through ``pltpu.make_async_copy`` using the per-
sequence block table, exactly the pointer-chase -> prefetch -> yield ->
use discipline of the paper:

  * issue the DMA for page i+1  (== ``__builtin_prefetch``),
  * compute attention on page i (== the thread the core switches to),
  * wait on the DMA only when page i+1's compute needs it
    (== the load that hits cache because the prefetch landed).

``n_buffers`` is the prefetch queue depth P of the paper's model (Eq. 3):
the planner (repro.core.planner) sizes it from the measured page-fetch
latency and per-page compute time via the same Theta equations, because
the law max{T_compute, L_fetch/P} is hardware-independent.

Block tables arrive via scalar prefetch (PrefetchScalarGridSpec) so the
page indices are known to the DMA engine ahead of the compute -- the
TPU equivalent of computing the next pointer before yielding.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention"]

NEG_INF = -1e30


def _kernel(
    # scalar-prefetch operands
    block_tables_ref,            # (B, ppseq) int32, SMEM
    lengths_ref,                 # (B,) int32, SMEM
    # array operands
    q_ref,                       # (1, rep, D) VMEM block
    k_pages_ref,                 # (P, page, Hkv, D) ANY (slow tier)
    v_pages_ref,
    # outputs
    o_ref,                       # (1, rep, D)
    # scratch
    k_buf, v_buf,                # (n_buf, page, D) VMEM staging
    sem,                         # DMA semaphores (n_buf, 2)
    *,
    page: int,
    n_buf: int,
    scale: float,
):
    b = pl.program_id(0)
    h = pl.program_id(1)
    length = lengths_ref[b]
    n_pages = jax.lax.div(length + page - 1, page)

    def start_fetch(p_idx, slot):
        page_id = block_tables_ref[b, p_idx]
        pltpu.make_async_copy(
            k_pages_ref.at[page_id, :, h], k_buf.at[slot], sem.at[slot, 0]
        ).start()
        pltpu.make_async_copy(
            v_pages_ref.at[page_id, :, h], v_buf.at[slot], sem.at[slot, 1]
        ).start()

    def wait_fetch(p_idx, slot):
        page_id = block_tables_ref[b, p_idx]
        pltpu.make_async_copy(
            k_pages_ref.at[page_id, :, h], k_buf.at[slot], sem.at[slot, 0]
        ).wait()
        pltpu.make_async_copy(
            v_pages_ref.at[page_id, :, h], v_buf.at[slot], sem.at[slot, 1]
        ).wait()

    # warm the pipeline: issue the first min(n_buf, n_pages) prefetches
    for slot in range(n_buf):
        @pl.when(slot < n_pages)
        def _prime(slot=slot):
            start_fetch(slot, slot)

    q = q_ref[0, 0].astype(jnp.float32) * scale     # (rep, D)

    def body(p_idx, carry):
        acc, m, l = carry
        slot = jax.lax.rem(p_idx, n_buf)
        # wait for this page's DMA, load it out of the staging buffer, and
        # only then re-issue the slot for page p_idx + n_buf (the paper's
        # yield: pages p+1 .. p+n_buf-1 are already in flight, so the MXU
        # works while the DMA engine fills the queue back to depth P).
        wait_fetch(p_idx, slot)
        k = k_buf[slot].astype(jnp.float32)          # (page, D)
        v = v_buf[slot].astype(jnp.float32)

        @pl.when(p_idx + n_buf < n_pages)
        def _next():
            start_fetch(p_idx + n_buf, slot)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (rep, page)
        pos = p_idx * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(pos < length, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ()))
        )
        return acc_new, m_new, l_new

    rep, D = q.shape
    acc0 = jnp.zeros((rep, D), jnp.float32)
    m0 = jnp.full((rep, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rep, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_pages, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def paged_decode_attention(
    q: jnp.ndarray,              # (B, Hq, D) one new token per sequence
    k_pages: jnp.ndarray,        # (P, page, Hkv, D) slow-tier page store
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,   # (B, ppseq) int32
    lengths: jnp.ndarray,        # (B,) int32
    *,
    n_buffers: int = 2,          # prefetch depth "P" of the paper's model
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    n_pages_store, page, Hkv, _ = k_pages.shape
    rep = Hq // Hkv
    ppseq = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)
    n_buf = max(2, min(n_buffers, ppseq))

    qg = q.reshape(B, Hkv, rep, D)

    kernel = functools.partial(
        _kernel, page=page, n_buf=n_buf, scale=scale
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv),
        in_specs=[
            pl.BlockSpec((1, 1, rep, D), lambda b, h, *_: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, D), lambda b, h, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_buf, page, D), k_pages.dtype),
            pltpu.VMEM((n_buf, page, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((n_buf, 2)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables, lengths, qg.reshape(B, Hkv, rep, D), k_pages, v_pages)
    return out.reshape(B, Hq, D)