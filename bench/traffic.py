"""The general load generator: what the measured window does, read from a
traffic file.

``kind: "steady"`` — one training job served in chunks of ``chunk`` rounds
with a checkpoint after each, in one serve call.  Its first ``warm_chunks``
chunks are set-up (they compile or load every program); the window opens
when the serve loop reports them done and closes when it reports the
window's chunks done, so it holds that many chunks and as many checkpoints.
The window is a fixed amount of work: ``window_chunks`` chunks in a run of
BENCHMARK.json's ``run_seconds``, in proportion for another ``--seconds``
(at least one).  A traced run holds ``TRACE_CHUNKS`` chunks (a profiler
trace of a whole fig1-xl chunk is already some hundred MB).

``kind: "restart"`` — a job restarted after every ``chunk`` rounds.  Set-up
is one serve call of ``setup_rounds`` rounds in chunks of ``chunk``, which
compiles (or loads) the serve programs, puts them in the program cache and
checkpoints each chunk.  The window holds ``window_restarts`` serve calls
(in proportion for another ``--seconds``, at least one; ``TRACE_RESTARTS``
in a traced run), each of which drops the programs the process holds,
resumes from the newest checkpoint, serves one chunk and checkpoints it:
what a restarted process does, but for the process start and the problem's
build, which the in-process restart keeps.  ``ttfr_s`` is the mean, over
the window's calls, of the time from a call's entry to the serve loop's
report that its chunk is done, on the harness's own clock.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List

TRACE_CHUNKS = 1
TRACE_RESTARTS = 2


@dataclasses.dataclass
class Outcome:
    records: List[dict]
    rounds: int            # rounds served in the window
    chunks: int            # chunks (checkpoints) served in the window
    attempted: int
    failed: int
    end_to_end: dict       # metric name → value
    window_s: float


def scaled(count: int, seconds: float, run_seconds: float) -> int:
    """A window's ``count`` of work at ``run_seconds``, in proportion for a
    run of ``seconds``; at least one."""
    return max(1, round(count * seconds / run_seconds))


def steady(served, traffic: dict, seconds: float, run_seconds: float, traced: bool,
           window, setup_done: Callable) -> Outcome:
    chunk = traffic["chunk"]
    warm = chunk * traffic["warm_chunks"]
    chunks = TRACE_CHUNKS if traced else scaled(traffic["window_chunks"], seconds, run_seconds)
    total = warm + chunks * chunk
    served.warm_closing([total], chunk)

    def on_chunk(done: int):
        if done == warm:
            setup_done()
            window.start()
        elif done == total:
            window.stop()

    rec = served.serve(max_rounds=total, chunk=chunk, on_chunk=on_chunk)
    if not window.closed:
        raise RuntimeError("the serve loop did not report its chunks; the window never closed")
    rounds = total - warm
    return Outcome(records=[rec], rounds=rounds, chunks=chunks,
                   attempted=rounds, failed=rounds - (rec["rounds"] - warm),
                   end_to_end={"round_ms": window.seconds * 1e3 / rounds},
                   window_s=window.seconds)


def restart(served, traffic: dict, seconds: float, run_seconds: float, traced: bool,
            window, setup_done: Callable) -> Outcome:
    chunk, setup = traffic["chunk"], traffic["setup_rounds"]
    restarts = TRACE_RESTARTS if traced else scaled(traffic["window_restarts"], seconds,
                                                    run_seconds)
    ends = [setup + chunk * (i + 1) for i in range(restarts)]
    served.warm_closing(ends, chunk)
    served.serve(max_rounds=setup, chunk=chunk)
    setup_done()
    window.start()
    records, ttfr = [], []
    for end in ends:
        served.forget_programs()
        t0 = time.perf_counter()
        done = []
        rec = served.serve(max_rounds=end, chunk=chunk,
                           on_chunk=lambda t: done.append(time.perf_counter() - t0))
        records.append(rec)
        if done and rec["rounds"] == end:
            ttfr.append(done[0])
    window.stop()
    if not ttfr:
        raise RuntimeError("no restart reported its chunk done")
    return Outcome(records=records, rounds=restarts * chunk, chunks=restarts,
                   attempted=restarts, failed=restarts - len(ttfr),
                   end_to_end={"ttfr_s": sum(ttfr) / len(ttfr)},
                   window_s=window.seconds)


KINDS = {"steady": steady, "restart": restart}
