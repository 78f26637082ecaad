"""The program's own spans (``repro.core.spans``) as the metric readers see
them: on ``time.perf_counter()``, the clock of the run's records, so a
span belongs to the window when its ``t0`` lies in
``[rec.window_t0, rec.window_end]``.

A save is joined across threads by its step: every span that carries the
step of a ``ckpt.save`` begun in the window belongs to that save, the
writer thread's jobs too, though they end after the window.

Each function returns ``None`` where the program records no spans (a tree
from before the recorder) or the run recorded none of the kind asked for.
"""

from __future__ import annotations


def recorded() -> list | None:
    """Every span the program kept, or ``None`` without the recorder."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.spans()


def in_window(rec) -> list | None:
    got = recorded()
    if got is None:
        return None
    return [s for s in got if rec.window_t0 <= s.t0 <= rec.window_end]


def of_saves(rec) -> tuple[list, int] | None:
    """The spans of the saves begun in the window, and how many saves."""
    got = recorded()
    if got is None:
        return None
    steps = {s.attrs["step"] for s in got if s.name == "ckpt.save"
             and rec.window_t0 <= s.t0 <= rec.window_end}
    if not steps:
        return None
    return [s for s in got if s.attrs.get("step") in steps], len(steps)


def seconds(spans: list, *names: str) -> float:
    return sum(s.t1 - s.t0 for s in spans if s.name in names)


def nbytes(spans: list, *names: str) -> int:
    return sum(s.attrs.get("bytes", 0) for s in spans if s.name in names)


def rate_GB_per_s(rec, *names: str) -> float | None:
    """Bytes over seconds of the spans ``names`` of the window's saves."""
    saves = of_saves(rec)
    if saves is None:
        return None
    t = seconds(saves[0], *names)
    return nbytes(saves[0], *names) / t / 1e9 if t > 0 else None


def per_save_s(rec, *names: str) -> float | None:
    """Seconds of the spans ``names`` per save begun in the window."""
    saves = of_saves(rec)
    if saves is None:
        return None
    spans, n = saves
    if not any(s.name in names for s in spans):
        return None
    return seconds(spans, *names) / n


def share_of(rec, part: tuple[str, ...], whole: str) -> float | None:
    """Percent of the window's ``whole`` spans' seconds in ``part``."""
    spans = in_window(rec)
    if spans is None:
        return None
    total = seconds(spans, whole)
    return 100.0 * seconds(spans, *part) / total if total > 0 else None


def per_restart_s(rec, name: str) -> float | None:
    """Seconds of the spans ``name`` per ``fe.load_mesh`` of the window."""
    spans = in_window(rec)
    if spans is None:
        return None
    n = sum(1 for s in spans if s.name == "fe.load_mesh")
    if not n or not any(s.name == name for s in spans):
        return None
    return seconds(spans, name) / n


def setup_compile_s(rec) -> float | None:
    """Seconds under ``jax.compile`` or ``jax.cache_load`` in set-up: the
    union of their intervals, since a compile that hits the persistent
    cache holds its cache load."""
    got = recorded()
    if got is None:
        return None
    t_start = rec.window_t0 - rec.setup_s
    iv = sorted((s.t0, s.t1) for s in got
                if s.name in ("jax.compile", "jax.cache_load")
                and t_start <= s.t0 < rec.window_t0)
    if not iv:
        return None
    total, end = 0.0, float("-inf")
    for a, b in iv:
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def window_compiles(rec) -> int | None:
    """``jax.compile`` events in the window; ``None`` when the process
    recorded no compile at all (no listener to count them)."""
    got = recorded()
    if got is None or not any(s.name == "jax.compile" for s in got):
        return None
    return sum(1 for s in got if s.name == "jax.compile"
               and rec.window_t0 <= s.t0 <= rec.window_end)
