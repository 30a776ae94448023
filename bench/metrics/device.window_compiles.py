"""Backend compiles inside the window, counted by JAX's monitoring events
(``/jax/core/compile/backend_compile_duration``). Set-up warms every
shape, so this reads 0 unless a shape was missed."""


def read(run):
    return run.compiles
