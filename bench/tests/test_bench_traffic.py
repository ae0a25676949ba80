"""The ``restart`` traffic kind, driven through `system.Served` by a stub
serve loop that resumes from its own round count and reports each chunk
with the serve loop's log lines."""
import time
import types

import pytest

import benchpath  # noqa: F401
import system
import traffic


class FakeServe:
    """Stands in for `fed_serve.serve`: resumes at the rounds served so far
    (its checkpoint), waits ``delay`` seconds before each chunk's report and
    serves nothing after ``lost`` rounds."""

    def __init__(self, events, delay, lost=None):
        self.events, self.delay, self.lost = events, delay, lost
        self.t = 0
        self.calls = []

    def __call__(self, *, exp_name, cell_name, seed, chunk, max_rounds, ckpt_dir,
                 progcache_dir, log):
        self.calls.append((max_rounds, chunk))
        log(f"[serve] {exp_name}/{cell_name}: resumed from checkpoint at round {self.t}")
        while self.t < max_rounds and (self.lost is None or self.t < self.lost):
            time.sleep(self.delay)
            steps = min(chunk, max_rounds - self.t)
            log(f"[serve] rounds {self.t}..{self.t + steps - 1} done")
            self.t += steps
            self.events.append(("chunk", self.t))
        return {"rounds": self.t, "history": {}, "meta": {}}


class Job:
    def __init__(self, events):
        self.events = events

    def experiment(self, config, seed):
        return types.SimpleNamespace(name=f"fake.{seed}",
                                     cells=(types.SimpleNamespace(name="cell"),))

    def warm_closing(self, served, totals, chunk):
        self.events.append(("warm_closing", list(totals), chunk))


class Window:
    def __init__(self, events):
        self.events = events
        self.closed = False
        self.seconds = 0.0

    def start(self):
        self.events.append(("start",))

    def stop(self):
        self.events.append(("stop",))
        self.closed = True
        self.seconds = 1.0


def _restart(tmp_path, monkeypatch, seconds, traced=False, lost=None):
    from repro.launch import fed_serve

    events = []
    fake = FakeServe(events, delay=0.02, lost=lost)
    monkeypatch.setattr(fed_serve, "serve", fake)
    served = system.Served({}, 7, ckpt_dir=str(tmp_path / "ckpt"),
                           progcache_dir=str(tmp_path / "progcache"), job=Job(events))
    spec = {"kind": "restart", "chunk": 1, "setup_rounds": 4, "window_restarts": 12}
    out = traffic.KINDS["restart"](served, spec, seconds, 45, traced, Window(events),
                                   lambda: events.append(("setup_done",)))
    return out, fake.calls, events


@pytest.mark.parametrize("seconds,traced,restarts", [(45, False, 12), (15, False, 4),
                                                     (45, True, traffic.TRACE_RESTARTS)])
def test_restart_window(seconds, traced, restarts, tmp_path, monkeypatch):
    out, calls, events = _restart(tmp_path, monkeypatch, seconds, traced)
    # set-up: the closing evaluation warmed for every history length the
    # window ends a call with, then one call of 4 rounds in chunks of 1
    assert events[0] == ("warm_closing", [4 + i for i in range(1, restarts + 1)], 1)
    assert calls == [(4, 1)] + [(4 + i, 1) for i in range(1, restarts + 1)]
    start, stop = events.index(("start",)), events.index(("stop",))
    assert events[start - 1] == ("setup_done",) and events[start - 2] == ("chunk", 4)
    assert events[start + 1:stop] == [("chunk", 4 + i) for i in range(1, restarts + 1)]
    assert stop == len(events) - 1
    assert (out.attempted, out.failed, out.rounds, out.chunks) == (restarts, 0, restarts,
                                                                   restarts)
    assert len(out.records) == restarts and out.records[-1]["rounds"] == 4 + restarts
    # from each call's entry to its chunk's report: the stub's wait and little else
    assert 0.02 <= out.end_to_end["ttfr_s"] < 0.5


def test_restart_that_serves_nothing_fails(tmp_path, monkeypatch):
    out, _, _ = _restart(tmp_path, monkeypatch, 15, lost=6)
    assert (out.attempted, out.failed) == (4, 2)
    assert 0.02 <= out.end_to_end["ttfr_s"] < 0.5
