"""Floating-point operations one BL1 round needs (§2.3 block mode, data
basis, p = 1), counted from the shapes: what the algorithm requires, not
what XLA emits.  A multiply-add counts as 2.

The server step takes one of two routes each round (`bl.proj_mu_route`):
a Cholesky factorization of sym(H) − μI certifies [H]_μ = sym(H), and the
step then solves with a Cholesky factor of sym(H); where the certificate
fails, the step forms [H]_μ from an eigendecomposition and solves through
it.  The serve record's ``meta.server_eigh`` says how many rounds took the
eigendecomposition."""
from __future__ import annotations

from program_spans import server_eigh_share


def terms(problem: dict, cell: dict, eigh: bool) -> dict:
    """FLOPs of a round by part; ``eigh``: the round's server step took the
    eigendecomposition."""
    n, m, d, r = (problem[k] for k in ("n_clients", "m", "d", "r"))
    out = {
        # Γ_i = (A_i V_i)ᵀ D_i (A_i V_i) / m: the rotated data and its r×r product
        "coeff_target": n * (2 * m * d * r + 2 * m * r * r + m * r),
        # ∇f_i(z): A_i z, then A_iᵀ times the m loss derivatives
        "gradients": n * 4 * m * d,
        # (1/n)Σ V_i S_i V_iᵀ as (n, r, d) factors: S_i V_iᵀ, then the d×d sum
        "shift_reconstruction": n * (2 * r * r * d + 2 * r * d * d),
        # the certificate: Cholesky factorization of sym(H) − μI
        "certificate": d ** 3 / 3,
    }
    if eigh:
        # eigendecomposition of the symmetric d×d estimate (standard 9d³);
        # [H]_μ = S + V diag(w_μ − w) Vᵀ, and the solve through V with one
        # refinement step against [H]_μ
        out.update(eigh=9 * d ** 3, proj_mu_refine=2 * d ** 3 + 10 * d * d)
    else:
        # Cholesky factorization of sym(H); the solve (two triangular
        # solves of d² each) and one refinement step (the residual's
        # matrix-vector product and a second solve)
        out.update(cholesky=d ** 3 / 3, cholesky_refine=6 * d * d)
    return out


def per_round(problem: dict, cell: dict, records) -> float | None:
    """FLOPs of an average round, by the route the served rounds took."""
    share = server_eigh_share(records)
    if share is None:
        return None
    flops = lambda eigh: float(sum(terms(problem, cell, eigh).values()))
    return share * flops(True) + (1.0 - share) * flops(False)
