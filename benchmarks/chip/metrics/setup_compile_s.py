"""Set-up seconds under XLA compiles and persistent-cache loads
(``jax.compile``, ``jax.cache_load``)."""

from benchmarks.chip.program_spans import setup_compile_s as read  # noqa: F401
