"""Profiler trace → busy/idle share, device op time and host function spans.

`jax.profiler` writes an ``XSpace`` protobuf (``*.xplane.pb``).  This module
parses it with a schema declared here (field numbers of tsl's
``xplane.proto`` and XLA's ``hlo.proto``), so it needs nothing but
``google.protobuf``, and reduces it to the numbers the per-layer metric
readers take:

* device operations, one per event of each device plane's ``XLA Ops`` line,
  labelled with the JAX ``op_name`` that the module's HLO metadata gives the
  instruction (``/host:metadata`` carries one ``Hlo Proto`` per module);
* host functions, one per event of the Python tracer's line (events named
  ``$file.py:LINE function``), and the benchmark's own ``TraceAnnotation``
  spans, all on the host clock that the device events are converted to.

Times are nanoseconds on that shared clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_O, _R = _F.LABEL_OPTIONAL, _F.LABEL_REPEATED
_I64, _U64, _DBL = _F.TYPE_INT64, _F.TYPE_UINT64, _F.TYPE_DOUBLE
_STR, _BYT, _MSG, _I32 = _F.TYPE_STRING, _F.TYPE_BYTES, _F.TYPE_MESSAGE, _F.TYPE_INT32

# (message, [(field, number, type, label, message type)]) — the subset of
# xplane.proto / hlo.proto / xla_data.proto this module reads
_SCHEMA = (
    ("XStat", [("metadata_id", 1, _I64, _O, None), ("double_value", 2, _DBL, _O, None),
               ("uint64_value", 3, _U64, _O, None), ("int64_value", 4, _I64, _O, None),
               ("str_value", 5, _STR, _O, None), ("bytes_value", 6, _BYT, _O, None),
               ("ref_value", 7, _U64, _O, None)]),
    ("XEvent", [("metadata_id", 1, _I64, _O, None), ("offset_ps", 2, _I64, _O, None),
                ("duration_ps", 3, _I64, _O, None), ("stats", 4, _MSG, _R, "XStat"),
                ("num_occurrences", 5, _I64, _O, None)]),
    ("XLine", [("id", 1, _I64, _O, None), ("name", 2, _STR, _O, None),
               ("timestamp_ns", 3, _I64, _O, None), ("events", 4, _MSG, _R, "XEvent"),
               ("duration_ps", 9, _I64, _O, None), ("display_id", 10, _I64, _O, None),
               ("display_name", 11, _STR, _O, None)]),
    ("XEventMetadata", [("id", 1, _I64, _O, None), ("name", 2, _STR, _O, None),
                        ("metadata", 3, _BYT, _O, None), ("display_name", 4, _STR, _O, None),
                        ("stats", 5, _MSG, _R, "XStat"), ("child_id", 6, _I64, _R, None)]),
    ("XStatMetadata", [("id", 1, _I64, _O, None), ("name", 2, _STR, _O, None),
                       ("description", 3, _STR, _O, None)]),
    ("XPlane", [("id", 1, _I64, _O, None), ("name", 2, _STR, _O, None),
                ("lines", 3, _MSG, _R, "XLine"),
                ("event_metadata", 4, _MSG, _R, "XPlane.EventMetadataEntry"),
                ("stat_metadata", 5, _MSG, _R, "XPlane.StatMetadataEntry"),
                ("stats", 6, _MSG, _R, "XStat")]),
    ("XSpace", [("planes", 1, _MSG, _R, "XPlane"), ("errors", 2, _STR, _R, None),
                ("warnings", 3, _STR, _R, None), ("hostnames", 4, _STR, _R, None)]),
    ("OpMetadata", [("op_type", 1, _STR, _O, None), ("op_name", 2, _STR, _O, None),
                    ("source_file", 3, _STR, _O, None), ("source_line", 4, _I32, _O, None)]),
    ("HloInstructionProto", [("name", 1, _STR, _O, None), ("opcode", 2, _STR, _O, None),
                             ("metadata", 7, _MSG, _O, "OpMetadata")]),
    ("HloComputationProto", [("name", 1, _STR, _O, None),
                             ("instructions", 2, _MSG, _R, "HloInstructionProto")]),
    ("HloModuleProto", [("name", 1, _STR, _O, None),
                        ("computations", 3, _MSG, _R, "HloComputationProto")]),
    ("HloProto", [("hlo_module", 1, _MSG, _O, "HloModuleProto")]),
)
_PKG = "benchxp"


def _build_classes():
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto", package=_PKG,
                                            syntax="proto3")
    for name, fields in _SCHEMA:
        m = fd.message_type.add(name=name)
        for fname, num, ftype, label, tname in fields:
            f = m.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                f.type_name = f".{_PKG}.{tname}"
        if name == "XPlane":
            for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                                 ("StatMetadataEntry", "XStatMetadata")):
                e = m.nested_type.add(name=entry)
                e.options.map_entry = True
                e.field.add(name="key", number=1, type=_I64, label=_O)
                e.field.add(name="value", number=2, type=_MSG, label=_O,
                            type_name=f".{_PKG}.{value}")
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PKG}.{name}")) for name, _ in _SCHEMA}


_CLASSES = None


def _cls(name):
    global _CLASSES
    if _CLASSES is None:
        _CLASSES = _build_classes()
    return _CLASSES[name]


@dataclasses.dataclass
class Span:
    """One host interval on the trace clock (ns)."""

    name: str
    start: float
    end: float
    line: str = ""

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class DeviceOps:
    """The ``XLA Ops`` events of one device plane, sorted by start (ns),
    its program executions (``XLA Modules``), and the time from which the
    profiler kept no more of its events (it drops them to bound a trace)."""

    start: np.ndarray
    end: np.ndarray
    meta: np.ndarray                  # event metadata id of each op
    names: Dict[int, str]             # metadata id → event name
    modules: Tuple[np.ndarray, np.ndarray] = (np.zeros(0), np.zeros(0))
    dropped_from: float = float("inf")


#: the device plane's marker of where the profiler began dropping events
_DROPPED = "Trace Buffers Dropped"
#: the host runtime's launch and completion of a device program
_LAUNCH, _DONE = "tpu::System::Execute", "tpu::System::Execute=>Done"


@dataclasses.dataclass
class Trace:
    """What the readers need from one trace."""

    device: Dict[str, DeviceOps]      # device plane name → its ops
    host: List[Span]                  # host events (Python functions, annotations)
    op_names: Dict[str, str]          # HLO instruction name → JAX op_name
    plane_stats: Dict[str, dict] = dataclasses.field(default_factory=dict)

    def label(self, ops: DeviceOps, meta_id: int) -> str:
        """The JAX op_name of a device op, else its HLO instruction name."""
        ins = instruction_name(ops.names.get(meta_id, ""))
        return self.op_names.get(ins, ins)

    def executions(self) -> Tuple[np.ndarray, np.ndarray]:
        """Device program executions as the host runtime saw them: the i-th
        launch to the i-th completion (one device runs its programs in order)."""
        launch = [sp.start for sp in self.host if sp.name == _LAUNCH]
        done = [sp.end for sp in self.host if sp.name == _DONE]
        k = min(len(launch), len(done))
        return np.asarray(launch[:k], np.float64), np.asarray(done[:k], np.float64)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


_PY_EVENT = re.compile(r"^\$(?P<file>[^\s:]*)(?::\d+)? (?P<fn>\S+)$")


def host_function(name: str) -> Tuple[str, str]:
    """``$fed_serve.py:541 save_checkpoint`` → ``("fed_serve.py",
    "save_checkpoint")``; other names (C functions, annotations) →
    ``("", name)``."""
    m = _PY_EVENT.match(name)
    return (m.group("file"), m.group("fn")) if m else ("", name)


def instruction_name(event_name: str) -> str:
    """``%fusion.645 = f32[..] fusion(...)`` → ``fusion.645``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _hlo_op_names(plane) -> Dict[str, str]:
    """Instruction name → op_name over every module's HLO proto."""
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    out: Dict[str, str] = {}
    for md in plane.event_metadata.values():
        for s in md.stats:
            if stat_names.get(s.metadata_id) != "Hlo Proto" or not s.bytes_value:
                continue
            hp = _cls("HloProto")()
            hp.ParseFromString(s.bytes_value)
            for comp in hp.hlo_module.computations:
                for ins in comp.instructions:
                    if ins.metadata.op_name:
                        out.setdefault(ins.name, ins.metadata.op_name)
    return out


def _device_plane(plane, names) -> DeviceOps:
    starts, durs, metas, mods = [], [], [], []
    dropped = float("inf")
    for line in plane.lines:
        t0 = line.timestamp_ns * 1e3
        if line.name == "XLA TraceMe":
            dropped = min([dropped] + [(t0 + e.offset_ps) / 1e3 for e in line.events
                                       if names.get(e.metadata_id) == _DROPPED])
        elif line.name == "XLA Modules":
            mods.extend((t0 + e.offset_ps, t0 + e.offset_ps + e.duration_ps)
                        for e in line.events)
        elif line.name == "XLA Ops":
            for e in line.events:
                starts.append(t0 + e.offset_ps)
                durs.append(e.duration_ps)
                metas.append(e.metadata_id)
    start = np.asarray(starts, np.float64) / 1e3
    order = np.lexsort((-np.asarray(durs, np.float64), start))
    start = start[order]
    mods_arr = np.asarray(sorted(mods), np.float64).reshape(-1, 2) / 1e3
    return DeviceOps(start=start, end=start + np.asarray(durs, np.float64)[order] / 1e3,
                     meta=np.asarray(metas, np.int64)[order], names=names,
                     modules=(mods_arr[:, 0], mods_arr[:, 1]), dropped_from=dropped)


def load(path: str) -> Trace:
    """Parse an ``.xplane.pb`` (or the newest one under a log directory)."""
    if os.path.isdir(path):
        path = find_xplane(path)
    space = _cls("XSpace")()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    device: Dict[str, DeviceOps] = {}
    host: List[Span] = []
    op_names: Dict[str, str] = {}
    plane_stats: Dict[str, dict] = {}
    for plane in space.planes:
        names = {k: v.name for k, v in plane.event_metadata.items()}
        if plane.name == "/host:metadata":
            op_names.update(_hlo_op_names(plane))
        elif plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
            plane_stats[plane.name] = {
                stat_names.get(st.metadata_id, str(st.metadata_id)):
                    st.str_value or st.int64_value or st.uint64_value or st.double_value
                for st in plane.stats}
            device[plane.name] = _device_plane(plane, names)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                t0 = line.timestamp_ns
                for e in line.events:
                    s = t0 + e.offset_ps / 1e3
                    host.append(Span(names.get(e.metadata_id, ""), s,
                                     s + e.duration_ps / 1e3, line.name))
    host.sort(key=lambda sp: sp.start)
    return Trace(device=device, host=host, op_names=op_names, plane_stats=plane_stats)


# ==========================================================================
# reductions
# ==========================================================================
def _merge(s: np.ndarray, e: np.ndarray, lo: float, hi: float):
    """Maximal intervals (start, end arrays) of the union of [s, e) in [lo, hi]."""
    order = np.argsort(s, kind="stable")
    s, e = np.clip(s[order], lo, hi), np.clip(e[order], lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return s, e
    cummax = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > cummax[:-1]
    first = np.flatnonzero(new)
    last = np.r_[first[1:] - 1, len(s) - 1]
    return s[first], cummax[last]


def _segments(trace: Trace, ops: DeviceOps, lo: float, hi: float, modules: bool = True):
    """Busy intervals of one device in [lo, hi]: its ops and program
    executions up to where the profiler began dropping its events, and from
    there the executions as the host runtime saw them launch and complete.
    ``modules=False`` keeps the recorded ops alone."""
    if not modules:
        return _merge(ops.start, ops.end, lo, min(hi, ops.dropped_from))
    launch, done = trace.executions()
    late = done > ops.dropped_from
    return _merge(np.r_[ops.start, ops.modules[0], np.maximum(launch[late], ops.dropped_from)],
                  np.r_[ops.end, ops.modules[1], done[late]], lo, hi)


def _device_planes(trace: Trace) -> List[DeviceOps]:
    return [ops for ops in trace.device.values() if len(ops.start) or len(ops.modules[0])]


def busy_ns(trace: Trace, lo: float, hi: float, modules: bool = True) -> float:
    """Device busy time inside [lo, hi], averaged over devices (see
    `_segments`)."""
    planes = _device_planes(trace)
    if not planes:
        return 0.0
    total = 0.0
    for ops in planes:
        s, e = _segments(trace, ops, lo, hi, modules)
        total += float(np.sum(e - s))
    return total / len(planes)


def op_coverage(trace: Trace, lo: float, hi: float) -> Optional[float]:
    """Share of the busy time that recorded op events cover (below 1 where
    the profiler dropped device events)."""
    busy = busy_ns(trace, lo, hi)
    return None if busy <= 0 else busy_ns(trace, lo, hi, modules=False) / busy


def idle_share_pct(trace: Optional[Trace], window) -> Optional[float]:
    """1 − busy / window, in %; None without a trace or any device op."""
    if trace is None or window is None:
        return None
    lo, hi = window
    if hi <= lo or not _device_planes(trace):
        return None
    return 100.0 * (1.0 - busy_ns(trace, lo, hi) / (hi - lo))


def idle_gaps(trace: Trace, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Intervals of [lo, hi] in which the first device runs nothing."""
    planes = _device_planes(trace)
    if not planes:
        return [(lo, hi)]
    s, e = _segments(trace, planes[0], lo, hi)
    edges_lo = np.r_[lo, e]
    edges_hi = np.r_[s, hi]
    return [(a, b) for a, b in zip(edges_lo.tolist(), edges_hi.tolist()) if b > a]


def self_times(ops: DeviceOps, lo: float, hi: float) -> np.ndarray:
    """Exclusive time (ns) of each op that starts in [lo, hi]: its duration
    less the ops nested inside it (a loop op contains its body's ops)."""
    start, end = ops.start.tolist(), ops.end.tolist()
    own = [e - s for s, e in zip(start, end)]
    stack: List[int] = []
    for i, (s, e) in enumerate(zip(start, end)):
        while stack and end[stack[-1]] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    out = np.asarray(own, np.float64)
    out[(ops.start < lo) | (ops.start > hi)] = 0.0
    return np.maximum(out, 0.0)


def op_clusters(trace: Trace, window, match, gap_ns: float = 5e6) -> List[Tuple[float, bool]]:
    """The matching ops of the first device, grouped where consecutive ones
    lie more than ``gap_ns`` apart (a repeated phase such as one round's
    eigendecomposition): [(exclusive ns, complete)], where a cluster is
    complete when it lies inside the window and ends before the profiler
    began dropping events."""
    planes = [ops for ops in trace.device.values() if len(ops.start)]
    if not planes:
        return []
    ops = planes[0]
    lo, hi = window
    hit = {m for m in np.unique(ops.meta).tolist() if match(trace.label(ops, m))}
    sel = np.flatnonzero(np.isin(ops.meta, list(hit)))
    if not len(sel):
        return []
    own = self_times(ops, float("-inf"), float("inf"))[sel]
    start, end = ops.start[sel], ops.end[sel]
    reach = np.maximum.accumulate(end)
    cuts = np.flatnonzero(start[1:] > reach[:-1] + gap_ns) + 1
    out = []
    for a, b in zip(np.r_[0, cuts], np.r_[cuts, len(sel)]):
        s0, e0 = start[a], reach[b - 1]
        complete = lo <= s0 and e0 <= hi and e0 < ops.dropped_from - 1e6
        out.append((float(own[a:b].sum()), bool(complete)))
    return out


def top_device_ops(trace: Trace, window, n: int = 10) -> List[list]:
    """[label, seconds] of the ops with the most exclusive time, first device."""
    planes = [ops for ops in trace.device.values() if len(ops.start)]
    if not planes:
        return []
    ops = planes[0]
    own = self_times(ops, *window)
    by_meta = np.bincount(np.unique(ops.meta, return_inverse=True)[1], weights=own)
    totals: Dict[str, float] = {}
    for m, t in zip(np.unique(ops.meta).tolist(), by_meta.tolist()):
        lab = trace.label(ops, m)
        totals[lab] = totals.get(lab, 0.0) + t
    best = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[lab, t / 1e9] for lab, t in best if t > 0]


def host_spans(trace: Trace, function: str, file: Optional[str] = None,
               lo: float = float("-inf"), hi: float = float("inf")) -> List[Span]:
    """Outermost calls of a Python function (by name, and file when given)
    that start in [lo, hi]."""
    out: List[Span] = []
    for sp in trace.host:
        if not lo <= sp.start <= hi:
            continue
        f, fn = host_function(sp.name)
        if fn != function or (file is not None and f != file):
            continue
        if out and sp.start < out[-1].end and sp.line == out[-1].line:
            continue                            # nested (recursive) call
        out.append(sp)
    return out


def mean_span_ms(trace: Optional[Trace], window, function: str,
                 file: Optional[str] = None) -> Optional[float]:
    """Mean host time (ms) of the outermost calls of a Python function that
    start in the window; None where the trace holds none."""
    if trace is None or window is None:
        return None
    spans = host_spans(trace, function, file, *window)
    if not spans:
        return None
    return sum(s.dur for s in spans) / 1e6 / len(spans)


def annotation(trace: Trace, name: str) -> List[Span]:
    """The benchmark's own ``TraceAnnotation`` spans of that name."""
    return [sp for sp in trace.host if sp.name == name]


def host_label_at(trace: Trace, t: float, line: str, files=None) -> str:
    """Innermost Python function running at time ``t`` on one host line;
    with ``files``, the innermost whose file is among them (else the
    innermost of any file)."""
    inner, inner_prog = None, None
    for sp in trace.host:
        if sp.start > t:
            break
        if sp.end < t or sp.line != line or not sp.name.startswith("$"):
            continue
        f, fn = host_function(sp.name)
        inner = fn
        if files is None or f in files:
            inner_prog = fn
    return inner_prog or inner or "no Python function"


def top_idle_gaps(trace: Trace, window, line: str, files=None, n: int = 10) -> List[list]:
    """[host function, seconds] for the longest device idle gaps in the
    window, each named by what the host thread was running at its middle."""
    gaps = sorted(idle_gaps(trace, *window), key=lambda g: g[0] - g[1])[:n]
    return [[host_label_at(trace, (a + b) / 2, line, files), (b - a) / 1e9]
            for a, b in gaps]
