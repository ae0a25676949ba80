"""The program's own spans and scopes in a reduced trace (`trace_reduce`).

Two kinds of marks, on one clock:

* **program spans** — host ``TraceAnnotation`` events the serve loop opens
  (`repro.core.spans`), named ``serve.*``, ``ckpt.*`` and ``cohort.*``; a
  name may carry ``#key=value#`` arguments, so a span is matched by the
  name before its first ``#``;
* **layer scopes** — ``jax.named_scope`` segments of a device operation's
  JAX ``op_name``: ``oracle``, ``basis``, ``compress``, ``reduce``,
  ``server``.  An operation belongs to its innermost layer segment, or to
  none.

Each reduction returns None where the trace holds no such span or scope (a
program that does not mark itself), so a reader built on it reports
nothing rather than zero.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

import trace_reduce

#: the device layer scopes, from the clients up to the server
LAYERS = ("oracle", "basis", "compress", "reduce", "server")
#: program span name prefixes
PREFIXES = ("serve.", "ckpt.", "cohort.")
#: program spans that run off the serving thread (the cohort engine's
#: prefetch worker); a trace's host lines are told apart by thread name
#: only, which that worker shares with the serving thread
OFF_THREAD = ("cohort.gather",)
#: the benchmark's window annotation (`run.Window.NAME`)
WINDOW = "bench.window"


def span_name(event: str) -> str:
    """``ckpt.write#t=16#`` → ``ckpt.write``."""
    return event.split("#", 1)[0]


def program_spans(trace, lo: float, hi: float, name: str) -> List[trace_reduce.Span]:
    """The spans named ``name`` that start in [lo, hi]."""
    return [sp for sp in trace.host if lo <= sp.start <= hi and span_name(sp.name) == name]


def _total_ns(spans) -> float:
    return float(sum(sp.dur for sp in spans))


def per_checkpoint_ms(trace, window, name: str) -> Optional[float]:
    """Time (ms) in spans ``name`` per ``serve.checkpoint`` span, over those
    that start in the window; None without either."""
    if trace is None or window is None:
        return None
    checkpoints = program_spans(trace, *window, name="serve.checkpoint")
    parts = program_spans(trace, *window, name=name)
    if not checkpoints or not parts:
        return None
    return _total_ns(parts) / 1e6 / len(checkpoints)


def cohort_swap_ms(trace, window) -> Optional[float]:
    """(``cohort.unload`` + ``cohort.load``) time (ms) per epoch loaded
    (``cohort.load`` span) in the window; None without a load."""
    if trace is None or window is None:
        return None
    loads = program_spans(trace, *window, name="cohort.load")
    if not loads:
        return None
    unloads = program_spans(trace, *window, name="cohort.unload")
    return (_total_ns(loads) + _total_ns(unloads)) / 1e6 / len(loads)


def idle_unspanned_pct(trace, window) -> Optional[float]:
    """Share (%) of the window's device-idle time in which no program span
    was open on the serving thread (the host line of the window's
    annotation; `OFF_THREAD` spans left out); None where that thread opened
    no program span in the window."""
    if trace is None or window is None:
        return None
    lo, hi = window
    ann = trace_reduce.annotation(trace, WINDOW)
    line = ann[0].line if ann else None
    mine = [sp for sp in trace.host
            if sp.line == line and sp.end > lo and sp.start < hi
            and span_name(sp.name).startswith(PREFIXES)
            and span_name(sp.name) not in OFF_THREAD]
    if not mine:
        return None
    gaps = trace_reduce.idle_gaps(trace, lo, hi)
    idle = sum(b - a for a, b in gaps)
    if idle <= 0:
        return 0.0
    s, e = trace_reduce._merge(np.asarray([sp.start for sp in mine]),
                               np.asarray([sp.end for sp in mine]), lo, hi)
    covered = 0.0
    for a, b in gaps:
        covered += float(np.sum(np.clip(np.minimum(e, b) - np.maximum(s, a), 0, None)))
    return 100.0 * (idle - covered) / idle


def layer_of(label: str) -> Optional[str]:
    """The innermost layer scope among an op_name's path segments."""
    for seg in reversed(label.split("/")):
        if seg in LAYERS:
            return seg
    return None


#: the last reduction, reused by every reader of one trace
_LAST: list = [None, None, None]


def layer_rounds(trace, window):
    """Exclusive device time (ns) per layer (None for unscoped operations)
    over the window's whole rounds, and their count; None where the trace
    holds no whole round.

    Rounds are delimited by the ``server`` scope, which runs once per round.
    Its operations do not lie together in time (the compiler schedules
    other layers' work among them, and the next round's server work can
    start as soon as its inputs exist), so a round is cut at one operation:
    the scan body runs each of its own instructions once a round, and a
    server-scoped instruction that recurs at the round's period marks it.
    A candidate occurs twice or more with an operation of every other layer
    between each two occurrences (a loop inside the server step recurs
    with nothing else between); of the candidates, those recurring at no
    less than three quarters of the longest such interval run once a round,
    and the one with the most occurrences, then the earliest, is the
    marker.  Its occurrences that lie in the window and end before the
    profiler began dropping the device's events bound the whole rounds; an
    operation counts when it starts and ends between the first and the
    last, so the chunk's prologue, before its first round, is left out.
    An operation with no layer scope of its own takes the layer of the
    innermost operation it runs inside (the asynchronous copies and slices
    the compiler adds inside a scoped loop carry no op_name).  First device
    only."""
    if trace is None or window is None:
        return None
    if _LAST[0] is trace and _LAST[1] == tuple(window):
        return _LAST[2]
    out = _layer_rounds(trace, window)
    _LAST[:] = [trace, tuple(window), out]
    return out


def _layer_rounds(trace, window):
    planes = [ops for ops in trace.device.values() if len(ops.start)]
    if not planes:
        return None
    ops = planes[0]
    lo, hi = window
    metas, inverse = np.unique(ops.meta, return_inverse=True)
    # each op's layer as a code: 0 unscoped, i + 1 for LAYERS[i]
    code_of = {None: 0, **{name: i + 1 for i, name in enumerate(LAYERS)}}
    codes = _inherit(ops, np.asarray([code_of[layer_of(trace.label(ops, m))]
                                      for m in metas.tolist()], np.int64)[inverse])
    kept = (ops.start >= lo) & (ops.end <= min(hi, ops.dropped_from - 1e6))
    server = np.flatnonzero(kept & (codes == code_of["server"]))
    # occurrences of each server instruction, in time order
    server = server[np.lexsort((ops.start[server], ops.meta[server]))]
    groups = np.split(server, np.flatnonzero(np.diff(ops.meta[server])) + 1)
    others = [np.sort(ops.start[kept & (codes == c)])
              for c in set(code_of.values()) - {0, code_of["server"]}]
    others = [o for o in others if len(o)]

    def each_interval_holds_every_layer(g):
        a, b = ops.start[g[:-1]], ops.start[g[1:]]
        return all((np.searchsorted(o, a) < np.searchsorted(o, b)).all() for o in others)

    recurring = [(float(np.diff(ops.start[g]).min()), g) for g in groups
                 if len(g) > 1 and each_interval_holds_every_layer(g)]
    if not recurring:
        return None
    period = max(rec for rec, _ in recurring)
    marks = min((g for rec, g in recurring if rec >= 0.75 * period),
                key=lambda g: (-len(g), ops.start[g[0]]))
    first, last = ops.start[marks[0]], ops.start[marks[-1]]
    inside = (ops.start >= first) & (ops.end <= last)
    per_code = np.bincount(codes[inside],
                           weights=trace_reduce.self_times(ops, first, last)[inside],
                           minlength=len(LAYERS) + 1)
    totals = {name: float(per_code[i + 1]) for i, name in enumerate(LAYERS)}
    totals[None] = float(per_code[0])
    return totals, len(marks) - 1


def _inherit(ops, codes):
    """Layer codes with each unscoped operation (code 0) given the code of
    the innermost operation enclosing it, in the nesting `trace_reduce.
    self_times` walks (operations sorted by start, enclosing ones first)."""
    end = ops.end.tolist()
    out = codes.tolist()
    stack: List[int] = []
    for i, s in enumerate(ops.start.tolist()):
        while stack and end[stack[-1]] <= s:
            stack.pop()
        if not out[i] and stack:
            out[i] = out[stack[-1]]
        stack.append(i)
    return np.asarray(out, np.int64)


def layer_ms(trace, window, name: str) -> Optional[float]:
    """Exclusive device time (ms) per whole round of the operations whose
    innermost layer scope is ``name`` (`layer_rounds`)."""
    got = layer_rounds(trace, window)
    if got is None:
        return None
    totals, rounds = got
    return totals[name] / 1e6 / rounds
