"""The trace reduction (bench/trace_reduce.py) on a trace built here with
known answers, and on a small trace recorded on a TPU v5e."""
import os

import numpy as np
import pytest

import benchpath  # noqa: F401
import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tpu_small.xplane.pb")


def _space(path):
    """An XSpace with one device plane of nested ops, a metadata plane whose
    HLO proto names two instructions, and a host plane with Python events."""
    X = tr._cls
    sp = X("XSpace")()
    meta = sp.planes.add(name="/host:metadata")
    meta.stat_metadata[1].name = "Hlo Proto"
    hp = X("HloProto")()
    comp = hp.hlo_module.computations.add(name="main")
    for name, op in (("while.1", "jit(f)/while"), ("fusion.7", "jit(f)/while/body/eigh"),
                     ("dot.3", "jit(f)/dot_general")):
        ins = comp.instructions.add(name=name)
        ins.metadata.op_name = op
    md = meta.event_metadata[1]
    md.name = "jit_f(1)"
    md.stats.add(metadata_id=1, bytes_value=hp.SerializeToString())
    dev = sp.planes.add(name="/device:TPU:0")
    for i, nm in ((1, "%while.1 = (f32[2]) while(...)"), (2, "%fusion.7 = f32[2] fusion(...)"),
                  (3, "%dot.3 = f32[2] dot(...)"), (4, "%copy.9 = f32[2] copy(...)")):
        dev.event_metadata[i].name = nm
    line = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    # window [1000, 2000] ns: while 1000-1400 holding fusion 1100-1300;
    # dot 1500-1600; copy 1900-2100 (half outside)
    for mid, off, dur in ((1, 0, 400), (2, 100, 200), (3, 500, 100), (4, 900, 200)):
        line.events.add(metadata_id=mid, offset_ps=off * 1000, duration_ps=dur * 1000)
    # program executions: the second one's last 100 ns lost their op events
    mods = dev.lines.add(name="XLA Modules", timestamp_ns=1000)
    for off, dur in ((0, 400), (500, 200), (900, 200)):
        mods.events.add(metadata_id=1, offset_ps=off * 1000, duration_ps=dur * 1000)
    host = sp.planes.add(name="/host:CPU")
    for i, nm in ((1, "bench.window"), (2, "$fed_serve.py:428 serve"),
                  (3, "$artifacts.py:181 save_checkpoint"), (4, "$format.py:12 write_array"),
                  (5, "$rounds.py:1282 run_chunk")):
        host.event_metadata[i].name = nm
    py = host.lines.add(name="python3", timestamp_ns=1000)
    for mid, off, dur in ((1, 0, 1000), (2, 10, 980), (5, 20, 30), (3, 400, 100),
                          (4, 410, 50)):
        py.events.add(metadata_id=mid, offset_ps=off * 1000, duration_ps=dur * 1000)
    with open(path, "wb") as f:
        f.write(sp.SerializeToString())


@pytest.fixture()
def trace(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    _space(path)
    return tr.load(path)


def test_window_annotation_and_busy_union(trace):
    (win,) = tr.annotation(trace, "bench.window")
    assert (win.start, win.end) == (1000, 2000)
    # union: 1000-1400 (fusion nested inside), 1500-1700 (ops to 1600, the
    # execution to 1700), 1900-2000
    assert tr.busy_ns(trace, 1000, 2000) == pytest.approx(700)
    assert tr.busy_ns(trace, 1000, 2000, modules=False) == pytest.approx(600)
    assert tr.op_coverage(trace, 1000, 2000) == pytest.approx(6 / 7)
    assert tr.idle_share_pct(trace, (1000, 2000)) == pytest.approx(30.0)
    assert tr.idle_gaps(trace, 1000, 2000) == [(1400, 1500), (1700, 1900)]


def test_op_matching_by_hlo_metadata_and_self_time(trace):
    ops = trace.device["/device:TPU:0"]
    labels = [trace.label(ops, m) for m in ops.meta.tolist()]
    assert labels == ["jit(f)/while", "jit(f)/while/body/eigh", "jit(f)/dot_general", "copy.9"]
    own = tr.self_times(ops, 1000, 2000).tolist()
    assert own == [200, 200, 100, 200]
    eigh = tr.op_clusters(trace, (1000, 2000), lambda lab: lab.split("/")[-1] == "eigh")
    assert eigh == [(pytest.approx(200), True)]
    top = tr.top_device_ops(trace, (1000, 2000))
    assert [lab for lab, _ in top][:2] in (["jit(f)/while", "jit(f)/while/body/eigh"],
                                           ["jit(f)/while/body/eigh", "jit(f)/while"])
    assert sum(s for _, s in top) == pytest.approx(700e-9)


def test_host_event_attribution(trace):
    (ck,) = tr.host_spans(trace, "save_checkpoint", "artifacts.py", 1000, 2000)
    assert ck.dur == pytest.approx(100)
    assert tr.host_spans(trace, "save_checkpoint", "other.py") == []
    (sv,) = tr.host_spans(trace, "serve", "fed_serve.py", 1000, 2000)
    (rc,) = tr.host_spans(trace, "run_chunk", "rounds.py", 1000, 2000)
    assert rc.start - sv.start == pytest.approx(10)
    # the gap 1400-1500 is spent in save_checkpoint → numpy's write_array
    assert tr.host_label_at(trace, 1450, "python3") == "write_array"
    assert tr.host_label_at(trace, 1450, "python3", files={"artifacts.py"}) == "save_checkpoint"
    gaps = tr.top_idle_gaps(trace, (1000, 2000), "python3", {"artifacts.py", "fed_serve.py"})
    assert gaps[0] == ["serve", pytest.approx(200e-9)]
    assert gaps[1] == ["save_checkpoint", pytest.approx(100e-9)]


def test_reduction_without_device_ops_reports_nothing(tmp_path):
    sp = tr._cls("XSpace")()
    sp.planes.add(name="/host:CPU")
    path = str(tmp_path / "empty.xplane.pb")
    with open(path, "wb") as f:
        f.write(sp.SerializeToString())
    t = tr.load(path)
    assert tr.idle_share_pct(t, (0, 10)) is None
    assert tr.op_clusters(t, (0, 10), lambda lab: True) == []
    assert tr.top_device_ops(t, (0, 10)) == []


def test_recorded_tpu_trace():
    t = tr.load(RECORDED)
    (win,) = tr.annotation(t, "bench.window")
    ops = t.device["/device:TPU:0"]
    assert len(ops.start) > 0
    busy = tr.busy_ns(t, win.start, win.end)
    assert 0 < busy < win.end - win.start
    idle = tr.idle_share_pct(t, (win.start, win.end))
    assert 0 < idle < 100
    labels = {t.label(ops, m) for m in np.unique(ops.meta).tolist()}
    assert any(lab.split("/")[-1] == "eigh" for lab in labels)
    eigh = tr.op_clusters(t, (win.start, win.end), lambda lab: lab.split("/")[-1] == "eigh")
    # two chunks; an 8×8 eigh is so short that a chunk's two rounds form one cluster
    assert len(eigh) == 2 and all(whole for _, whole in eigh)
    assert 0 < sum(ns for ns, _ in eigh) <= busy
    ck = tr.host_spans(t, "save_checkpoint", None, win.start, win.end)
    assert len(ck) == 2 and all(s.dur > 0.05e9 for s in ck)
    gaps = tr.top_idle_gaps(t, (win.start, win.end), win.line)
    assert gaps[0][0] in ("save_checkpoint", "sleep") and gaps[0][1] > 0.05


def test_dropped_device_events_fall_back_to_host_executions(tmp_path):
    """Past the profiler's drop marker, busy time is the host runtime's
    launch-to-completion of each program; op time covers the rest."""
    X = tr._cls
    sp = X("XSpace")()
    dev = sp.planes.add(name="/device:TPU:0")
    dev.event_metadata[1].name = "%fusion.1 = f32[2] fusion(...)"
    dev.event_metadata[2].name = "Trace Buffers Dropped"
    dev.lines.add(name="XLA Ops", timestamp_ns=0).events.add(
        metadata_id=1, offset_ps=1000 * 1000, duration_ps=200 * 1000)
    dev.lines.add(name="XLA TraceMe", timestamp_ns=0).events.add(
        metadata_id=2, offset_ps=1200 * 1000, duration_ps=10 ** 9)
    host = sp.planes.add(name="/host:CPU")
    host.event_metadata[1].name = "tpu::System::Execute"
    host.event_metadata[2].name = "tpu::System::Execute=>Done"
    main = host.lines.add(name="main/1", timestamp_ns=0)
    done = host.lines.add(name="futex/2", timestamp_ns=0)
    for launch, end in ((990, 1200), (1300, 1600)):
        main.events.add(metadata_id=1, offset_ps=launch * 1000, duration_ps=5 * 1000)
        done.events.add(metadata_id=2, offset_ps=(end - 5) * 1000, duration_ps=5 * 1000)
    path = str(tmp_path / "dropped.xplane.pb")
    with open(path, "wb") as f:
        f.write(sp.SerializeToString())
    t = tr.load(path)
    assert t.device["/device:TPU:0"].dropped_from == 1200
    assert tr.busy_ns(t, 1000, 2000) == pytest.approx(500)
    assert tr.busy_ns(t, 1000, 2000, modules=False) == pytest.approx(200)
    assert tr.op_coverage(t, 1000, 2000) == pytest.approx(0.4)
    assert tr.idle_gaps(t, 1000, 2000) == [(1200, 1300), (1600, 2000)]


def test_op_clusters_keep_whole_phases(tmp_path):
    """Three eigh phases 1 ms long, 50 ms apart; the profiler drops events
    from inside the third, so two are whole."""
    X = tr._cls
    sp = X("XSpace")()
    meta = sp.planes.add(name="/host:metadata")
    meta.stat_metadata[1].name = "Hlo Proto"
    hp = X("HloProto")()
    comp = hp.hlo_module.computations.add(name="main")
    ins = comp.instructions.add(name="fusion.1")
    ins.metadata.op_name = "jit(f)/cond/eigh"
    meta.event_metadata[1].stats.add(metadata_id=1, bytes_value=hp.SerializeToString())
    dev = sp.planes.add(name="/device:TPU:0")
    dev.event_metadata[1].name = "%fusion.1 = f32[2] fusion(...)"
    dev.event_metadata[2].name = "Trace Buffers Dropped"
    line = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    for k in range(3):
        for j in range(4):                      # four 0.25 ms ops per phase
            line.events.add(metadata_id=1, offset_ps=(k * 50_000_000 + j * 250_000) * 1000,
                            duration_ps=250_000 * 1000)
    dev.lines.add(name="XLA TraceMe", timestamp_ns=0).events.add(
        metadata_id=2, offset_ps=100_500_000 * 1000, duration_ps=1000)
    path = str(tmp_path / "clusters.xplane.pb")
    with open(path, "wb") as f:
        f.write(sp.SerializeToString())
    t = tr.load(path)
    cl = tr.op_clusters(t, (0, 2e8), lambda lab: lab.endswith("eigh"))
    assert [round(ns) for ns, _ in cl] == [1_000_000, 1_000_000, 1_000_000]
    assert [whole for _, whole in cl] == [True, True, False]
