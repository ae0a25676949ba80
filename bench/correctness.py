"""The comparison that decides ``correct``: what the served job produced
against the plain reference of its configuration, over the job's first
``compare_rounds`` rounds (the configuration's: past the set-up chunk, its
checkpoint and the chunk boundary into the window, and for the cohort engine
past cohort epochs, their scatter and gather and the frozen fleet totals),
and over every round recorded for the per-leg bits.

Numbers, each held to the limit the configuration file states, over rounds
1..T, T = ``compare_rounds`` (or every round served, where the job served
fewer):

* ``gap_rel``: max over rounds of |gap − gap_ref| / max(gap_ref, GAP_FLOOR·f*),
  the gaps f(z_t) − f* of the serve record against the reference's; below
  the floor a gap is within the float64 rounding of the loss itself;
* ``step_rel``: |‖z_1 − z_0‖ − ‖z_1 − z_0‖_ref| / ‖z_1 − z_0‖_ref, the first
  update as the server applied it;
* ``change_rel``: the same for the change after T rounds, z_T − z_0;
* ``bits``: the largest absolute difference, over every leg and every round
  recorded, between the ledger's cumulative bits and the reference's.

The norms need the iterates of rounds 0, 1 and T alone, and the job's
adapter and the reference hand over just those (``{0: z_0, 1: z_1, T:
z_T}``), so a job whose iterate is a large pytree keeps three flat vectors
on the host, not T + 1.
"""
from __future__ import annotations

import numpy as np

#: gaps under this share of f* are compared against it instead
GAP_FLOOR = 1e-10


def compared_rounds(config: dict, served: int) -> int:
    """T: the configuration's ``compare_rounds``, or every round served."""
    return min(int(config["compare_rounds"]), served)


def numbers(gaps, legs: dict, iterates: dict, ref: dict, rounds: int) -> dict:
    """Program outputs (serve-record gaps and legs, the checkpointed
    iterates ``{0: z_0, 1: z_1, rounds: z_rounds}``) against a reference
    run of at least ``rounds`` rounds, whose ``iterates`` is such a mapping
    too.  A job with no known optimum reports ``f_star`` 0: its gaps are
    then losses, and ``gap_rel`` is the relative error of the loss."""
    g, gr = np.asarray(gaps, np.float64), np.asarray(ref["gaps"], np.float64)
    if rounds < 1 or len(g) < rounds + 1 or len(gr) < rounds + 1:
        raise ValueError(f"the job served {len(g) - 1} rounds and the reference "
                         f"{len(gr) - 1}; the check compares {rounds}")
    z = {t: np.asarray(iterates[t], np.float64) for t in (0, 1, rounds)}
    zr = {t: np.asarray(ref["iterates"][t], np.float64) for t in (0, 1, rounds)}
    t = slice(1, rounds + 1)
    floor = GAP_FLOOR * abs(ref["f_star"])
    gap_rel = float(np.max(np.abs(g[t] - gr[t]) / np.maximum(np.abs(gr[t]), floor)))

    def norm_gap(k):
        a = np.linalg.norm(z[k] - z[0])
        b = np.linalg.norm(zr[k] - zr[0])
        return float(abs(a - b) / b)

    want = ref["legs"](np.arange(len(g)))
    bits = max(float(np.max(np.abs(np.asarray(legs[leg], np.float64) - want[leg])))
               for leg in want)
    return {"gap_rel": gap_rel, "step_rel": norm_gap(1),
            "change_rel": norm_gap(rounds), "bits": bits}


def judge(values: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}); a number that is not finite,
    or has no limit, fails."""
    checks = {}
    ok = True
    for name, value in values.items():
        limit = limits.get(name)
        good = limit is not None and np.isfinite(value) and value <= limit
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def reference_numbers(reference, seed: int, config: dict, *, dtype=np.float64,
                      fault=None, against=None) -> dict:
    """The numbers of a reference run in the program's place (the control
    in a lower precision, or a planted fault) against the float64 one, over
    the configuration's ``compare_rounds``."""
    rounds = int(config["compare_rounds"])
    problem, cell = config["problem"], config["cell"]
    if against is None:
        against = reference.run(seed, problem, cell, rounds)
    run = reference.run(seed, problem, cell, rounds, dtype=dtype, fault=fault)
    legs = run["legs"](np.arange(rounds + 1))
    return numbers(run["gaps"], legs, run["iterates"], against, rounds)
