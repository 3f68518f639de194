"""Sweep and cohort layer: XLA backend compilations that ran during the
measured window (a ``jax.monitoring`` listener on
``/jax/core/compile/backend_compile_duration``); set-up should have
compiled or loaded every program, so this reads 0."""


def read(ctx):
    return ctx.get("compiles_in_window")
