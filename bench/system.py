"""The benchmark's one handle on the system under test: the served path,
`repro.launch.fed_serve.serve`, on a registered copy of the configuration's
experiment, which the job adapter of the configuration's kind builds
(``bench/jobs``).  The run's seed draws the problem's data and is the serve
loop's root PRNG seed, as `fed_serve --seed` is."""
from __future__ import annotations

import dataclasses
import os
import re
import shutil
import sys


#: the serve loop's report that a chunk's rounds are done
_CHUNK_DONE = re.compile(r"\[serve\] rounds \d+\.\.(\d+) done")


def import_program(root: str) -> None:
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


@dataclasses.dataclass
class Served:
    """One served job: its experiment, checkpoint and program-cache
    directories, and the serve calls the traffic makes on it.  ``job`` is
    the adapter of the configuration's kind (``bench/jobs/<kind>.py``),
    which knows how the job is built and what its checkpoints hold."""

    config: dict
    seed: int
    ckpt_dir: str
    progcache_dir: str
    job: object
    log: object = None

    def __post_init__(self):
        self.exp = self.job.experiment(self.config, self.seed)
        shutil.rmtree(self.ckpt_dir, ignore_errors=True)
        os.makedirs(self.ckpt_dir)
        os.makedirs(self.progcache_dir, exist_ok=True)

    def build(self):
        """The job's problem; memoized by the program, so every later serve
        call reuses it."""
        return self.job.build(self.exp)

    def serve(self, max_rounds: int, chunk: int, on_chunk=None) -> dict:
        """Serve the job to ``max_rounds``, resuming from the newest
        checkpoint if there is one; ``on_chunk(t)`` is called on the serve
        loop's report that its rounds up to t are done (before that chunk's
        checkpoint)."""
        from repro.launch import fed_serve

        def log(msg, *rest):
            if self.log is not None:
                self.log(msg, *rest)
            m = _CHUNK_DONE.match(str(msg))
            if m and on_chunk is not None:
                on_chunk(int(m.group(1)) + 1)

        return fed_serve.serve(
            exp_name=self.exp.name, cell_name=self.exp.cells[0].name, seed=self.seed,
            chunk=chunk, max_rounds=max_rounds, ckpt_dir=self.ckpt_dir,
            progcache_dir=self.progcache_dir, log=log)

    def forget_programs(self) -> None:
        """Drop the serve programs this process holds, so that the next
        serve call loads them from the program cache, as a restarted
        process does."""
        from repro.core import rounds

        rounds.clear_aot_memo()

    def warm_closing(self, totals, chunk: int) -> None:
        """Compile what a serve call computes besides its chunks, for each
        history length in ``totals`` that a call ends with."""
        self.job.warm_closing(self, totals, chunk)

    def iterates(self, rounds: int) -> dict:
        """``{0: z_0, 1: z_1, rounds: z_rounds}``, the evaluation iterates
        the newest checkpoint holds (flat float64 vectors)."""
        return self.job.iterates(self, rounds)

    def close(self) -> None:
        shutil.rmtree(self.ckpt_dir, ignore_errors=True)
