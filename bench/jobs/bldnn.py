"""Job adapter for BL-DNN fine-tuning with a frozen trunk (``"job":
"bldnn"``): a `repro.exp.registry.LMFinetuneSpec` problem with one
`MethodCell` of method ``bldnn``, served by the stacked engine (see
``jobs/glm.py`` for what each hook does).

The serve loop keeps no iterate per round for this job (a round's
evaluation is its loss and counters), so the iterates come from the
checkpoints it writes anyway: z_0 is the problem's federated weights and
z_T the carry's weights in the checkpoint of round T, which the key 1
holds too, as the reference's does (``bench/reference/granite_bldnn.py``):
the first update the job keeps is the one to its checkpoint, so
``step_rel`` reads what ``change_rel`` reads."""
from __future__ import annotations

import gc
import os

import numpy as np


def experiment(config: dict, seed: int):
    from repro.exp import registry

    c = config["cell"]
    problem = dict(config["problem"])
    problem.pop("kind", None)
    problem["overrides"] = tuple(tuple(kv) for kv in problem.get("overrides", ()))
    exp = registry.Experiment(
        name=f"bench.{config['name']}.{seed}", figure="extra",
        title=f"benchmark copy of {config['registry']}",
        paper_ref=config["source"],
        problem=registry.LMFinetuneSpec(**problem, seed=seed),
        cells=(registry.MethodCell(
            c["name"], c["method"], 0, basis=c.get("basis"),
            hess_comp=registry.CompressorCfg(**c["hess_comp"]),
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
    """The problem: the cut model's weights and the clients' token ids."""
    import jax

    from repro.exp import build_problem

    prob = build_problem(exp.problem)
    jax.block_until_ready((prob.params0, prob.trunk, prob.batch.data))
    return prob


def warm_closing(served, totals, chunk: int) -> None:
    """A chunk's round indices past round 0 and, for each history length,
    the ledger's uplink total (the closing evaluation is the streams'
    own losses: nothing to compile)."""
    import jax.numpy as jnp

    from repro.core import comm

    np.asarray(jnp.arange(chunk, 2 * chunk))
    for total in totals:
        zeros = np.zeros(total)
        np.asarray(comm.CommLedger(*(jnp.asarray(zeros)
                                     for _ in comm.CommLedger.LEGS)).uplink)


def iterates(served, rounds: int) -> dict:
    from repro.exp import build_problem
    from repro.exp.artifacts import list_checkpoints

    prob = build(served.exp)
    names = sorted(prob.params0)
    flat = lambda leaves: np.concatenate(
        [np.asarray(leaf, np.float64).ravel() for leaf in leaves])
    z0 = flat([prob.params0[k] for k in names])
    found = dict(list_checkpoints(served.ckpt_dir))
    if rounds not in found:
        raise ValueError(f"no checkpoint of round {rounds} in {served.ckpt_dir} "
                         f"(rounds {sorted(found)}); the check compares {rounds}")
    with np.load(os.path.join(served.ckpt_dir, f"ckpt-{rounds:08d}.npz")) as z:
        # the params lead the carry (BLDNNSpec.init)
        zt = flat([z[f"carry/{i}"] for i in range(len(names))])
    out = {0: z0, 1: zt, rounds: zt}
    # the reference builds its own model: let the program's copy and its
    # round programs go (the serve calls are over)
    from repro.core import rounds as engine

    del prob
    build_problem.cache_clear()
    engine.clear_aot_memo()
    gc.collect()
    return out
