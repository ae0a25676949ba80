"""Host spans: the serve loop's phases, named on the profiler's clock and
counted in a process-wide table.

    with spans.span("ckpt.write") as sp:
        ...
    sp.elapsed_s

A span opens a `jax.profiler.TraceAnnotation` of its name (and ``args``),
so under a profiler it lands on the host plane of the trace, on the clock
the device's events are converted to, and adds its duration to a table
``{name: {"count", "total_s", "max_s"}}`` that `snapshot` returns and
`reset` clears (the pattern of `repro.core.rounds.trace_counts`).  Spans
are always on: outside a profiler a span costs a clock read and a lock,
and the serve loop opens a handful per chunk, never per client and never
inside jitted code.

Names start with their layer: ``serve.`` (the serve loop), ``ckpt.`` (the
checkpoint step), ``cohort.`` (the cohort engine's host path).  The device
side of the same picture is the ``jax.named_scope`` that the round
combinators give their operations: ``oracle``, ``basis``, ``compress``,
``reduce``, ``server``.
"""
from __future__ import annotations

import threading
import time

import jax

_LOCK = threading.Lock()
_TABLE: dict = {}


class Span:
    """One timed phase; ``elapsed_s`` is set when it closes."""

    __slots__ = ("name", "args", "elapsed_s", "_t0", "_annotation")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args
        self.elapsed_s = None

    def __enter__(self) -> "Span":
        self._annotation = jax.profiler.TraceAnnotation(self.name, **self.args)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed_s = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        with _LOCK:
            row = _TABLE.setdefault(
                self.name, {"count": 0, "total_s": 0.0, "max_s": 0.0})
            row["count"] += 1
            row["total_s"] += self.elapsed_s
            row["max_s"] = max(row["max_s"], self.elapsed_s)


def span(name: str, **args) -> Span:
    """A context manager timing the block under ``name``; ``args`` ride on
    the trace annotation."""
    return Span(name, args)


def snapshot() -> dict:
    """Copy of {span name: {"count", "total_s", "max_s"}} since the last
    reset."""
    with _LOCK:
        return {name: dict(row) for name, row in _TABLE.items()}


def reset() -> None:
    with _LOCK:
        _TABLE.clear()


def since(before: dict) -> dict:
    """{name: {"count", "total_s"}} of the spans that closed after
    ``before`` (a `snapshot`).  A maximum cannot be differenced, so a
    delta leaves ``max_s`` out."""
    out = {}
    for name, row in snapshot().items():
        was = before.get(name, {"count": 0, "total_s": 0.0})
        if row["count"] > was["count"]:
            out[name] = {"count": row["count"] - was["count"],
                         "total_s": row["total_s"] - was["total_s"]}
    return out
