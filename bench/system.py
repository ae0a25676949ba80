"""The benchmark's one handle on the system under test: the served path,
`repro.launch.fed_serve.serve`, on a registered copy of the configuration's
experiment.  The run's seed draws the fleet (the problem's data) and is the
serve loop's root PRNG seed, as `fed_serve --seed` is."""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import sys

import numpy as np


#: the serve loop's report that a chunk's rounds are done
_CHUNK_DONE = re.compile(r"\[serve\] rounds \d+\.\.(\d+) done")


def import_program(root: str) -> None:
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


@dataclasses.dataclass
class Served:
    """One served job: its experiment, checkpoint and program-cache
    directories, and the serve calls the traffic makes on it."""

    config: dict
    seed: int
    ckpt_dir: str
    progcache_dir: str
    log: object = None

    def __post_init__(self):
        from repro.exp import registry

        p, c = self.config["problem"], self.config["cell"]
        self.exp_name = f"bench.{self.config['name']}.{self.seed}"
        self.cell_name = c["name"]
        comp = lambda cfg: None if cfg is None else registry.CompressorCfg(**cfg)
        exp = registry.Experiment(
            name=self.exp_name, figure="extra",
            title=f"benchmark copy of {self.config['registry']}",
            paper_ref=self.config["source"],
            problem=registry.ProblemSpec(**p, seed=self.seed),
            cells=(registry.MethodCell(
                c["name"], c["method"], 0, basis=c.get("basis"),
                hess_comp=comp(c.get("hess_comp")),
                model_comp=comp(c.get("model_comp")),
                params=tuple(sorted(c.get("params", {}).items())),
                backend=c["backend"]),),
            tags=("bench",))
        known = registry.EXPERIMENT_REGISTRY.get(self.exp_name)
        if known is None:
            registry.register_experiment(exp)
        elif known != exp:
            raise ValueError(f"{self.exp_name} is registered with another configuration")
        self.exp = exp
        shutil.rmtree(self.ckpt_dir, ignore_errors=True)
        os.makedirs(self.ckpt_dir)
        os.makedirs(self.progcache_dir, exist_ok=True)

    def build(self):
        """The problem (data, bases' inputs, reference optimum x*); memoized
        by the program, so every later serve call reuses it."""
        from repro.exp import build_problem

        prob = build_problem(self.exp.problem)
        np.asarray(prob.x_star)
        return prob

    def serve(self, max_rounds: int, chunk: int, on_chunk=None) -> dict:
        """Serve the job to ``max_rounds``; ``on_chunk(t)`` is
        called on the serve loop's report that its rounds up to t are done
        (before that chunk's checkpoint)."""
        from repro.launch import fed_serve

        def log(msg, *rest):
            if self.log is not None:
                self.log(msg, *rest)
            m = _CHUNK_DONE.match(str(msg))
            if m and on_chunk is not None:
                on_chunk(int(m.group(1)) + 1)

        return fed_serve.serve(
            exp_name=self.exp_name, cell_name=self.cell_name, seed=self.seed,
            chunk=chunk, max_rounds=max_rounds, ckpt_dir=self.ckpt_dir,
            progcache_dir=self.progcache_dir, log=log)

    def warm_closing(self, total: int, chunk: int) -> None:
        """Compile what the window's serve call computes besides its chunks,
        for the history length ``total`` it ends with: the rounds' index
        range of a chunk that does not start at round 0, the closing gap
        evaluation (on the device for the stacked engine; the cohort engine
        evaluates on the host) and the ledger's uplink total."""
        import jax.numpy as jnp

        from repro.core import client_batch, comm
        from repro.exp.engine import StreamProblem
        from repro.launch import fed_serve

        prob = self.build()
        np.asarray(jnp.arange(chunk, 2 * chunk))
        stacked = not isinstance(prob, StreamProblem)
        if stacked:
            spec, batch, _ = fed_serve.build_setup(
                self.exp, self.exp.cell(self.cell_name), prob)
            f_star = client_batch.global_loss(batch, prob.x_star)
        zeros = np.zeros(total)
        np.asarray(comm.CommLedger(*(jnp.asarray(zeros)
                                     for _ in comm.CommLedger.LEGS)).uplink)
        if stacked:
            np.asarray(spec.eval_streams(
                batch, jnp.zeros((total, prob.d), prob.x0.dtype), f_star)["gap"])

    def iterates(self) -> np.ndarray:
        """The evaluation iterates of every round served so far, as the
        newest checkpoint holds them."""
        paths = sorted(glob.glob(os.path.join(self.ckpt_dir, "ckpt-*.npz")))
        if not paths:
            raise FileNotFoundError(f"no checkpoint in {self.ckpt_dir}")
        with np.load(paths[-1]) as z:
            return np.asarray(z["stream/eval_x"], np.float64)

    def close(self) -> None:
        shutil.rmtree(self.ckpt_dir, ignore_errors=True)
