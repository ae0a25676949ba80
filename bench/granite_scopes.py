"""Device time of a model scope inside the client oracle, for the per-layer
metrics of the frozen-trunk BL-DNN cell (``mamba_ms.granite``,
``moe_ms.granite``).

`program_spans.layer_ms` gives each operation one of the round's layer
scopes (``oracle``, ``basis``, ...) and no finer name, so the model's own
scopes under ``oracle`` (`repro.models.model`: ``mamba``, ``attn``,
``moe``, ``head``) are cut here.  An operation belongs to the innermost
of them among its JAX ``op_name`` segments; a segment that a
transformation wraps (``transpose(jvp(mamba))``, the backward pass) counts
under the name inside.  Times are exclusive (`trace_reduce.self_times`),
summed over the window's operations of the first device that start in it
and end before the profiler began dropping events, and divided by the
rounds the window holds (a steady window holds whole chunks).  None where
no operation carries the name: a program that does not mark itself
reports nothing."""
from __future__ import annotations

import re
from typing import Optional

import numpy as np

import trace_reduce

#: the client oracle's sub-scopes (`repro.models.model`)
SUBSCOPES = ("mamba", "attn", "moe", "head")
_WRAPPED = re.compile(r"^(?:[\w.]+\()+|\)+$")


def segments(label: str):
    return [_WRAPPED.sub("", seg) for seg in label.split("/")]


def innermost(label: str, names) -> Optional[str]:
    for seg in reversed(segments(label)):
        if seg in names:
            return seg
    return None


def oracle_scope_ms(run, name: str) -> Optional[float]:
    """Exclusive device time (ms per round) of the operations under the
    ``oracle`` scope whose innermost model scope is ``name``."""
    trace, window = run.trace, run.window
    if trace is None or window is None or run.rounds <= 0:
        return None
    planes = [ops for ops in trace.device.values() if len(ops.start)]
    if not planes:
        return None
    ops = planes[0]
    lo, hi = window
    metas, inverse = np.unique(ops.meta, return_inverse=True)
    mine = np.asarray([
        innermost(lab, SUBSCOPES) == name and "oracle" in segments(lab)
        for lab in (trace.label(ops, m) for m in metas.tolist())], bool)[inverse]
    kept = mine & (ops.start >= lo) & (ops.end <= min(hi, ops.dropped_from - 1e6))
    if not kept.any():
        return None
    return float(np.sum(trace_reduce.self_times(ops, lo, hi)[kept])) / 1e6 / run.rounds
