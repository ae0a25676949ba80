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
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List

TRACE_CHUNKS = 1


@dataclasses.dataclass
class Outcome:
    records: List[dict]
    rounds: int            # rounds served in the window
    chunks: int            # chunks (checkpoints) served in the window
    attempted: int
    failed: int
    end_to_end: dict       # metric name → value
    window_s: float


def window_chunks(traffic: dict, seconds: float, run_seconds: float) -> int:
    return max(1, round(traffic["window_chunks"] * seconds / run_seconds))


def steady(served, traffic: dict, seconds: float, run_seconds: float, traced: bool,
           window, setup_done: Callable) -> Outcome:
    chunk = traffic["chunk"]
    warm = chunk * traffic["warm_chunks"]
    chunks = TRACE_CHUNKS if traced else window_chunks(traffic, seconds, run_seconds)
    total = warm + chunks * chunk
    served.warm_closing(total, chunk)

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


KINDS = {"steady": steady}
