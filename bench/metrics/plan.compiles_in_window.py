"""plan.compiles_in_window: XLA backend compiles that ended inside the
window (``/jax/core/compile/backend_compile_duration`` events, counted by
the harness through ``jax.monitoring``). Set-up compiles every shape the
window uses, so this should read 0."""


def read(run):
    return float(run.compiles_in_window)
