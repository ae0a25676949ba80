"""The readers of the program's own spans and layer scopes
(bench/program_spans.py and the metrics built on it), on traces built here
with known answers: a window of three rounds whose device events the
profiler stopped keeping inside the third, and a serve loop's host spans."""
import types

import pytest

import benchpath  # noqa: F401
import program_spans as ps
import run
import trace_reduce as tr

MS = 10 ** 9        # picoseconds in a millisecond
PREFIX = "jit(_chunk_body)/while/body/closed_call/"
#: HLO instruction → JAX op_name, one per layer and an op under two scopes
OPS = {
    "fusion.1": PREFIX + "oracle/nmd,nm->nd/dot_general",
    "fusion.2": PREFIX + "basis/nsr,nsd->nrd/dot_general",
    "fusion.3": PREFIX + "basis/oracle/mul",
    "fusion.4": PREFIX + "compress/top_k",
    "fusion.5": PREFIX + "reduce/reduce/reduce_sum",
    "fusion.6": PREFIX + "server/cond/branch_0_fun/eigh",
    "while.7": "jit(_chunk_body)/while",
    "fusion.8": PREFIX + "server/sub",
}
#: one round, 20 ms: (instruction, start ms, duration ms); the server step
#: is fusion.8 once and fusion.6 three times (a loop), with an asynchronous
#: slice-done.5 inside the first; it and copy.9 have no op_name, so no
#: scope of their own
ROUND = [("fusion.1", 0, 1), ("fusion.2", 1, 1.5), ("fusion.3", 2.5, 0.5),
         ("fusion.4", 3, 1), ("fusion.5", 4, 0.5), ("fusion.8", 5, 1),
         ("fusion.6", 6, 2.5), ("slice-done.5", 6.5, 0.5), ("fusion.6", 8.5, 2.5),
         ("fusion.6", 11, 2.5), ("copy.9", 15, 1)]
#: serving-thread spans (name, start ms, end ms); the window is 0-100 ms
HOST = [("serve.checkpoint", 1, 10), ("ckpt.payload#t=16#", 1, 3), ("ckpt.write", 3, 6),
        ("ckpt.digest", 6, 8), ("ckpt.write", 8, 9), ("ckpt.prune", 9, 9.5),
        ("serve.dispatch", 10, 40), ("cohort.unload", 10, 12),
        ("cohort.load#epoch=4#", 12, 15), ("cohort.prefetch_wait", 13, 14),
        ("cohort.unload", 20, 21), ("cohort.load#epoch=5#", 21, 23),
        ("serve.pull", 40, 60)]


def _write(path, *, scoped=True, spans=True, loop=True, dropped_ms=50.0):
    """Device: three rounds from t=0 (inside a loop op with ``loop``), the
    profiler's drop marker at ``dropped_ms``; host: the window annotation,
    the serving thread's spans and, on a worker thread of the same name, a
    gather."""
    X = tr._cls
    sp = X("XSpace")()
    meta = sp.planes.add(name="/host:metadata")
    meta.stat_metadata[1].name = "Hlo Proto"
    hp = X("HloProto")()
    comp = hp.hlo_module.computations.add(name="main")
    for ins, op in OPS.items():
        i = comp.instructions.add(name=ins)
        i.metadata.op_name = op if scoped else op.replace("oracle/", "").replace(
            "basis/", "").replace("compress/", "").replace("reduce/", "").replace(
            "server/", "")
    meta.event_metadata[1].stats.add(metadata_id=1, bytes_value=hp.SerializeToString())
    dev = sp.planes.add(name="/device:TPU:0")
    names = sorted(set(OPS) | {"copy.9", "slice-done.5"})
    ids = {n: i + 1 for i, n in enumerate(names)}
    for n, i in ids.items():
        dev.event_metadata[i].name = f"%{n} = f64[2] op(...)"
    dev.event_metadata[99].name = "Trace Buffers Dropped"
    line = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    if loop:
        line.events.add(metadata_id=ids["while.7"], offset_ps=0, duration_ps=int(60 * MS))
    for k in range(3):
        for ins, start, dur in ROUND:
            line.events.add(metadata_id=ids[ins], offset_ps=int((20 * k + start) * MS),
                            duration_ps=int(dur * MS))
    dev.lines.add(name="XLA TraceMe", timestamp_ns=0).events.add(
        metadata_id=99, offset_ps=int(dropped_ms * MS), duration_ps=1000)
    host = sp.planes.add(name="/host:CPU")
    events = [("bench.window", 0, 100)] + (HOST if spans else [])
    hid = {}
    for n, _, _ in events + [("cohort.gather", 0, 0)]:
        hid.setdefault(n, len(hid) + 1)
        host.event_metadata[hid[n]].name = n
    main = host.lines.add(name="python3", timestamp_ns=0)
    for n, a, b in events:
        main.events.add(metadata_id=hid[n], offset_ps=int(a * MS), duration_ps=int((b - a) * MS))
    if spans:       # the prefetch worker: same thread name, ignored
        host.lines.add(name="python3", timestamp_ns=0).events.add(
            metadata_id=hid["cohort.gather"], offset_ps=int(62 * MS), duration_ps=int(30 * MS))
    with open(path, "wb") as f:
        f.write(sp.SerializeToString())
    return tr.load(path)


def _view(trace):
    (win,) = tr.annotation(trace, "bench.window")
    return types.SimpleNamespace(trace=trace, window=(win.start, win.end))


def _read(name, trace):
    return run._module("metrics", name).read(_view(trace))


@pytest.fixture()
def trace(tmp_path):
    return _write(str(tmp_path / "t.xplane.pb"))


DEVICE_MS = {"oracle_ms": 1.0 + 0.5, "basis_ms": 1.5, "compress_ms": 1.0}
HOST_MS = {"ckpt_payload_ms.stream": 2.0, "ckpt_write_ms.stream": 3.0 + 1.0,
           "ckpt_digest_ms.stream": 2.0,
           "cohort_swap_ms": (2.0 + 3.0 + 1.0 + 2.0) / 2}


@pytest.mark.parametrize("name,value", sorted({**DEVICE_MS, **HOST_MS,
                                               "server_ms": 8.5}.items()))
def test_reader_on_a_marked_trace(name, value, trace):
    assert _read(name, trace) == pytest.approx(value)


def test_layer_rounds_keep_whole_rounds_and_leave_the_loop_out(trace):
    """fusion.8 recurs once a round (fusion.6 recurs within one), so its
    occurrences at 5, 25 and 45 ms bound two whole rounds before the drop
    marker at 50 ms; the loop op runs past them and is not counted; an op
    under basis/oracle belongs to the oracle; the slice inside a server op
    is the server's, the copy between rounds nobody's."""
    totals, rounds = ps.layer_rounds(trace, _view(trace).window)
    assert rounds == 2
    assert totals["server"] == pytest.approx(2 * 8.5e6)
    assert totals["reduce"] == pytest.approx(2 * 0.5e6)
    assert totals[None] == pytest.approx(2 * 1e6)           # copy.9 only
    assert ps.layer_of(OPS["fusion.3"]) == "oracle"
    assert ps.layer_of("copy.9") is None


def test_dropped_events_before_a_whole_round_read_nothing(tmp_path):
    """Marks past the drop marker do not count: at 20 ms one mark is kept
    and no round is whole; at 30 ms two are, so one round."""
    t = _write(str(tmp_path / "d.xplane.pb"), dropped_ms=20.0)
    assert ps.layer_rounds(t, _view(t).window) is None
    assert _read("server_ms", t) is None
    t = _write(str(tmp_path / "e.xplane.pb"), dropped_ms=30.0)
    totals, rounds = ps.layer_rounds(t, _view(t).window)
    assert rounds == 1 and totals["server"] == pytest.approx(8.5e6)


def test_idle_unspanned_share(tmp_path):
    """Idle time the serving thread's spans (1-60 ms) leave bare, as a share
    of the device's idle time in the window; the worker's gather (62-92 ms)
    does not count."""
    trace = _write(str(tmp_path / "i.xplane.pb"), loop=False)
    lo, hi = _view(trace).window
    gaps = tr.idle_gaps(trace, lo, hi)
    idle = sum(b - a for a, b in gaps)
    bare = sum(max(0.0, min(b, 1e6) - a) for a, b in gaps) \
        + sum(max(0.0, b - max(a, 60e6)) for a, b in gaps)
    want = 100.0 * bare / idle
    assert 0 < want < 100
    for name in ("idle_unspanned_pct", "idle_unspanned_pct.stream"):
        assert _read(name, trace) == pytest.approx(want)


def test_span_names_drop_their_arguments():
    assert ps.span_name("ckpt.payload#t=16#") == "ckpt.payload"
    assert ps.span_name("serve.pull") == "serve.pull"


NEW = sorted({**DEVICE_MS, **HOST_MS}) + ["server_ms", "idle_unspanned_pct",
                                          "idle_unspanned_pct.stream"]


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_without_marks(name, tmp_path):
    """A program that opens no span and names no scope (as before these
    readers existed) gives None, not zero and not an error."""
    t = _write(str(tmp_path / "u.xplane.pb"), scoped=False, spans=False)
    assert _read(name, t) is None
    assert run._module("metrics", name).read(
        types.SimpleNamespace(trace=None, window=None)) is None
