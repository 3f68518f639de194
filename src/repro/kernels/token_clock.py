"""Per-op token-clock update as a Pallas kernel (batched over grid cells).

The hot scalar update of the simulator's IO path (see
``repro.core.sim.devices``) is the token-clock grant: admit a request at
``submit`` by taking ``svc = max(submit, clock)`` and advancing the clock by
the per-request spacing.  The jax sweep backend
(:mod:`repro.core.sim.replay_jax`) performs this update once per scheduler
step for *every* cell of the latency x threads grid at once, so the batched
form is pure VPU work over ``(n_cells, n_ssd)`` clock arrays:

  * ``devmask`` one-hot selects each cell's round-robin device (all-zero
    rows for cells whose current suboperation is not an IO submission);
  * the IOPS clock is granted first, then the bandwidth clock, matching the
    scalar loops' ``svc = max(svc, tok); tok = svc + 1/R_io`` order exactly;
  * clocks of unselected devices pass through unchanged.

The TPU is the target; on CPU the kernel runs in ``interpret=True`` mode,
which is how the tests validate it against :func:`token_clock_update_ref` -- the pure-jnp twin used by the jax
backend's default (non-Pallas) path.  Both paths are bit-identical: same
ops, same order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["token_clock_update", "token_clock_update_ref"]


def _grant(submit, clocks, devmask, spacing):
    """One token-clock grant: ``svc = max(submit, clocks[dev])``, and the
    selected device's clock advances to ``svc + spacing``.

    ``submit`` is ``(G, 1)``, ``clocks``/``devmask`` are ``(G, n_ssd)``,
    ``spacing`` broadcasts.  A spacing of 0 disables the clock (grant
    passes through); a fully-masked row (no IO this step) is not gated:
    its ``at_dev`` reads 0.0, and simulated time is non-negative, so
    ``max(submit, 0) == submit``.
    """
    enabled = spacing > 0.0
    at_dev = jnp.sum(jnp.where(devmask, clocks, 0.0), axis=-1, keepdims=True)
    svc = jnp.where(enabled, jnp.maximum(submit, at_dev), submit)
    new_clocks = jnp.where(enabled & devmask, svc + spacing, clocks)
    return svc, new_clocks


def _update(submit, devmask, tok, bw, inv_r, cost_bw):
    """IOPS clock first, then bandwidth -- the bandwidth grant sees the
    IOPS-delayed service time, like ``SSDClocks.submit`` / the compiled
    loop.  All shapes as in :func:`_grant`."""
    svc, tok = _grant(submit, tok, devmask, inv_r)
    svc, bw = _grant(svc, bw, devmask, cost_bw)
    return svc, tok, bw


def _kernel(submit_ref, devmask_ref, tok_ref, bw_ref, inv_r_ref, cost_ref,
            svc_ref, tok_out_ref, bw_out_ref):
    svc, tok, bw = _update(
        submit_ref[:], devmask_ref[:] != 0, tok_ref[:], bw_ref[:],
        inv_r_ref[0, 0], cost_ref[0, 0],
    )
    svc_ref[:] = svc
    tok_out_ref[:] = tok
    bw_out_ref[:] = bw


def token_clock_update(submit, devmask, tok_next, bw_next, inv_r, cost_bw,
                       *, interpret: bool | None = None):
    """Pallas form of :func:`token_clock_update_ref` (same contract).

    ``interpret=None`` auto-selects interpreter mode off-TPU so the kernel
    runs (slowly, but correctly) on CPU CI; pass ``False`` to force
    compilation on a TPU backend.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    G, S = tok_next.shape
    dt = tok_next.dtype
    svc, tok, bw = pl.pallas_call(
        _kernel,
        out_shape=(
            jax.ShapeDtypeStruct((G, 1), dt),
            jax.ShapeDtypeStruct((G, S), dt),
            jax.ShapeDtypeStruct((G, S), dt),
        ),
        interpret=interpret,
    )(
        submit.reshape(G, 1).astype(dt),
        devmask.astype(jnp.int32),
        tok_next,
        bw_next,
        jnp.asarray(inv_r, dt).reshape(1, 1),
        jnp.asarray(cost_bw, dt).reshape(1, 1),
    )
    return svc[:, 0], tok, bw


def token_clock_update_ref(submit, devmask, tok_next, bw_next, inv_r,
                           cost_bw):
    """Pure-jnp reference: grant ``submit`` (``(G,)``) against the per-cell
    per-device clocks (``(G, n_ssd)``), device selected by the boolean
    one-hot ``devmask``.  ``inv_r``/``cost_bw`` are the clock spacings
    (``1/R_io`` and ``A_io/B_io``); a spacing of 0 disables that clock.
    Returns ``(svc, tok_next', bw_next')``.
    """
    svc, tok, bw = _update(
        submit[:, None], devmask, tok_next, bw_next,
        jnp.asarray(inv_r, tok_next.dtype),
        jnp.asarray(cost_bw, bw_next.dtype),
    )
    return svc[:, 0], tok, bw
