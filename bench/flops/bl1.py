"""Floating-point operations one BL1 round needs (§2.3 block mode, data
basis, p = 1), counted from the shapes: what the algorithm requires, not
what XLA emits.  A multiply-add counts as 2."""
from __future__ import annotations


def terms(problem: dict, cell: dict) -> dict:
    n, m, d, r = (problem[k] for k in ("n_clients", "m", "d", "r"))
    return {
        # Γ_i = (A_i V_i)ᵀ D_i (A_i V_i) / m: the rotated data and its r×r product
        "coeff_target": n * (2 * m * d * r + 2 * m * r * r + m * r),
        # ∇f_i(z): A_i z, then A_iᵀ times the m loss derivatives
        "gradients": n * 4 * m * d,
        # (1/n)Σ V_i S_i V_iᵀ as (n, r, d) factors: S_i V_iᵀ, then the d×d sum
        "shift_reconstruction": n * (2 * r * r * d + 2 * r * d * d),
        # eigendecomposition of the symmetric d×d estimate (standard 9d³)
        "eigh": 9 * d ** 3,
        # [H]_μ = S + V diag(w_μ − w) Vᵀ, and the solve through V with one
        # refinement step against [H]_μ
        "proj_mu_refine": 2 * d ** 3 + 10 * d * d,
    }


def per_round(problem: dict, cell: dict) -> float:
    return float(sum(terms(problem, cell).values()))
