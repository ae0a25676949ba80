"""Floating-point operations one BL2 round needs (Algorithm 2, standard
basis, full d×d coefficient state, partial participation), counted from the
shapes for the expected τ participants of the round.  A multiply-add
counts as 2."""
from __future__ import annotations


def terms(problem: dict, cell: dict) -> dict:
    m, d = problem["m"], problem["d"]
    tau = cell["params"]["tau"]
    return {
        # server: the d×d system, symmetrised, solved by Cholesky
        "server_solve": d ** 3 / 3 + 2 * d * d,
        # per participant: Hessian at the new model (m rank-1 terms)
        "hessians": tau * 2 * m * d * d,
        # per participant: gradient at the new model
        "gradients": tau * 4 * m * d,
        # per participant: shift update, Frobenius norm, g_i refresh (d×d
        # matrix-vector products and elementwise work)
        "client_state": tau * (6 * d * d + 4 * d),
    }


def per_round(problem: dict, cell: dict) -> float:
    return float(sum(terms(problem, cell).values()))
