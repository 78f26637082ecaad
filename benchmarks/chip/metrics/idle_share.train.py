"""Share of the traced window in which no operation ran on the cell's
devices, percent."""

from benchmarks.chip.trace import idle_share as read  # noqa: F401
