"""Job adapter for the federated GLM configurations (``"job": "glm"``, the
default): a `repro.exp.registry.ProblemSpec` problem with one `MethodCell`,
served by the stacked or the cohort engine.

An adapter is what `system.Served` needs to know of a job's kind:

* ``experiment(config, seed)``: the registered experiment (one cell) that
  `repro.launch.fed_serve.serve` runs; ``seed`` draws the problem's data;
* ``build(exp)``: build the problem (memoized by the program), so that
  every later serve call reuses it;
* ``warm_closing(served, totals, chunk)``: compile what a serve call
  computes besides its chunks, for each history length in ``totals`` that
  a call of the window ends with;
* ``iterates(served, rounds)``: ``{0: z_0, 1: z_1, rounds: z_rounds}``,
  the evaluation iterates the newest checkpoint holds, as flat float64
  vectors.
"""
from __future__ import annotations

import glob
import os

import numpy as np


def experiment(config: dict, seed: int):
    from repro.exp import registry

    c = config["cell"]
    comp = lambda cfg: None if cfg is None else registry.CompressorCfg(**cfg)
    exp = registry.Experiment(
        name=f"bench.{config['name']}.{seed}", figure="extra",
        title=f"benchmark copy of {config['registry']}",
        paper_ref=config["source"],
        problem=registry.ProblemSpec(**config["problem"], seed=seed),
        cells=(registry.MethodCell(
            c["name"], c["method"], 0, basis=c.get("basis"),
            hess_comp=comp(c.get("hess_comp")),
            model_comp=comp(c.get("model_comp")),
            params=tuple(sorted(c.get("params", {}).items())),
            backend=c["backend"]),),
        tags=("bench",))
    known = registry.EXPERIMENT_REGISTRY.get(exp.name)
    if known is None:
        registry.register_experiment(exp)
    elif known != exp:
        raise ValueError(f"{exp.name} is registered with another configuration")
    return exp


def build(exp):
    """The problem (data, bases' inputs, reference optimum x*)."""
    from repro.exp import build_problem

    prob = build_problem(exp.problem)
    np.asarray(prob.x_star)
    return prob


def warm_closing(served, totals, chunk: int) -> None:
    """The rounds' index range of a chunk that does not start at round 0,
    and for each history length the ledger's uplink total and the closing
    gap evaluation (on the device for the stacked engine; the cohort engine
    evaluates on the host).  The stacked engine's set-up (a second copy of
    the fleet on the device) lives only as long as this call."""
    import jax.numpy as jnp

    from repro.core import client_batch, comm
    from repro.exp.engine import StreamProblem
    from repro.launch import fed_serve

    prob = build(served.exp)
    np.asarray(jnp.arange(chunk, 2 * chunk))
    stacked = not isinstance(prob, StreamProblem)
    if stacked:
        spec, batch, _ = fed_serve.build_setup(served.exp, served.exp.cells[0], prob)
        f_star = client_batch.global_loss(batch, prob.x_star)
    for total in totals:
        zeros = np.zeros(total)
        np.asarray(comm.CommLedger(*(jnp.asarray(zeros)
                                     for _ in comm.CommLedger.LEGS)).uplink)
        if stacked:
            np.asarray(spec.eval_streams(
                batch, jnp.zeros((total, prob.d), prob.x0.dtype), f_star)["gap"])


def iterates(served, rounds: int) -> dict:
    paths = sorted(glob.glob(os.path.join(served.ckpt_dir, "ckpt-*.npz")))
    if not paths:
        raise FileNotFoundError(f"no checkpoint in {served.ckpt_dir}")
    with np.load(paths[-1]) as z:
        x = z["stream/eval_x"]
        if len(x) < rounds + 1:
            raise ValueError(f"the checkpoint holds {len(x) - 1} rounds; the check "
                             f"compares {rounds}")
        return {t: np.asarray(x[t], np.float64) for t in (0, 1, rounds)}
