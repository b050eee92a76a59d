"""``window_compiles``: programs XLA compiled inside the measured window,
from ``jax.monitoring``'s compile events.  Must be 0: every shape is warmed
up during set-up."""


def read(run):
    return {"window_compiles": run.compile_window["programs"]}
