"""XLA compiles in the window (``jax.compile``): none is expected."""

from benchmarks.chip.program_spans import window_compiles as read  # noqa: F401
