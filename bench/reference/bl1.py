"""Plain NumPy reference of Basis Learn Algorithm 1 on federated logistic
regression, independent of the program under test.

It builds the fleet from the run's seed with its own copy of the
synthetic generator, and runs BL1 as the paper states it: per-client data bases
(right singular vectors of A_i), Hessian coefficients Γ_i = V_iᵀ∇²f_i V_i,
Top-K compressed shift learning, the server's reconstruction
(1/n)Σ V_i L_i V_iᵀ + λI, its projection [H]_μ and a Newton step solved by
LU, and the per-leg bit accounting of Table 1.  ``dtype`` runs the same
arithmetic in another precision (the control); ``fault`` plants one of the
faults the correctness check has to catch.
"""
from __future__ import annotations

import numpy as np

FLOAT_BITS, INDEX_BITS = 64, 32


def make_fleet(seed: int, n_clients: int, m: int, d: int, r: int,
               lam: float = 1e-3, noise: float = 0.1, heterogeneity: float = 0.5):
    """(A (n, m, d), b (n, m)): rows of client i span a rank-r subspace
    rotated away from a shared one, labels from a planted model with flip
    noise."""
    rng = np.random.default_rng(seed)
    q_global, _ = np.linalg.qr(rng.standard_normal((d, r)))
    x_true = rng.standard_normal(d) / np.sqrt(d)
    A = np.empty((n_clients, m, d))
    b = np.empty((n_clients, m))
    for i in range(n_clients):
        P, _ = np.linalg.qr((1 - heterogeneity) * q_global
                            + heterogeneity * rng.standard_normal((d, r)))
        alpha = rng.standard_normal((m, r))
        A[i] = alpha @ P.T
        p = 1.0 / (1.0 + np.exp(-(A[i] @ x_true)))
        b[i] = np.where(rng.random(m) < (1 - noise) * p + noise * 0.5, 1.0, -1.0)
    return A, b


def _sigmoid(t):
    return 0.5 * (np.tanh(t / 2.0) + 1.0)


def loss(A, b, lam, x) -> float:
    z = np.einsum("nmd,d->nm", A, x) * b
    return float(np.mean(np.mean(np.logaddexp(0.0, -z), axis=1))
                 + 0.5 * lam * np.dot(x, x))


def grads(A, b, lam, x):
    """Per-client gradients (n, d)."""
    z = np.einsum("nmd,d->nm", A, x) * b
    coef = -b * _sigmoid(-z)
    return np.einsum("nmd,nm->nd", A, coef) / A.shape[1] + lam * x


def newton_optimum(A, b, lam, iters: int = 30, tol: float = 1e-15):
    """x* of the fleet objective by Newton's method on the full Hessian."""
    n, m, d = A.shape
    flat = A.reshape(n * m, d)
    x = np.zeros(d, A.dtype)
    for _ in range(iters):
        g = grads(A, b, lam, x).mean(axis=0)
        w = _sigmoid(flat @ x * b.reshape(-1))
        w = w * (1.0 - w)
        H = flat.T @ (w[:, None] * flat) / (n * m) + lam * np.eye(d, dtype=A.dtype)
        step = np.linalg.solve(H, g)
        x = x - step
        if np.linalg.norm(step) <= tol * max(1.0, np.linalg.norm(x)):
            break
    return x


def data_bases(A, rcond: float = 1e-10):
    """(n, d, r): orthonormal bases of each client's row space."""
    n, m, d = A.shape
    out = []
    for i in range(n):
        _, s, vt = np.linalg.svd(A[i], full_matrices=False)
        rank = max(1, int(np.sum(s > s.max() * max(m, d) * rcond)))
        out.append(vt[:rank].T)
    ranks = {v.shape[1] for v in out}
    if len(ranks) != 1:
        raise ValueError(f"clients' data ranks differ: {sorted(ranks)}")
    return np.stack(out)


def topk(delta, k: int):
    """Keep the k largest |entries| of each client's coefficient matrix."""
    n = delta.shape[0]
    flat = delta.reshape(n, -1)
    if k >= flat.shape[1]:
        return delta.copy()
    keep = np.argpartition(-np.abs(flat), k - 1, axis=1)[:, :k]
    out = np.zeros_like(flat)
    np.put_along_axis(out, keep, np.take_along_axis(flat, keep, axis=1), axis=1)
    return out.reshape(delta.shape)


def run(seed: int, problem: dict, cell: dict, rounds: int, *,
        dtype=np.float64, fault: str | None = None) -> dict:
    """BL1 for ``rounds`` rounds; returns the iterates ``{0: z_0, 1: z_1,
    rounds: z_rounds}``, the gaps f(z_t) − f* of every round, and the
    per-leg cumulative bits before each round.
    ``seed`` draws the fleet; the rounds draw nothing (p = 1, Top-K and the
    identity downlink are deterministic).

    ``fault``: None; ``"frozen"`` (the step leaves the iterate unchanged);
    ``"half"`` (the server averages over the first half of the clients);
    ``"altered"`` (the server's solve returns its answer scaled by 1.001).
    """
    n, m, d, r = (problem[k] for k in ("n_clients", "m", "d", "r"))
    lam = problem["lam"]
    params = cell.get("params", {})
    alpha, eta, p = params.get("alpha", 1.0), params.get("eta", 1.0), params.get("p", 1.0)
    if p != 1.0 or cell["model_comp"]["kind"] != "identity" \
            or cell["hess_comp"]["kind"] != "topk" or cell["basis"] != "data_outer":
        raise ValueError("the BL1 reference covers the data basis, Top-K "
                         "Hessian compression, an identity downlink and p=1")
    mu = params.get("mu", lam)
    k = cell["hess_comp"]["k"]
    A, b = make_fleet(seed, n, m, d, r, lam)
    A, b = A.astype(dtype), b.astype(dtype)
    lam_t = dtype(lam)
    f_star = loss(A, b, lam_t, newton_optimum(A, b, lam_t))
    V = data_bases(A)
    rb = V.shape[2]
    AV = np.einsum("nmd,ndr->nmr", A, V)
    agg = slice(0, n // 2) if fault == "half" else slice(0, n)
    n_agg = agg.stop - agg.start

    def coeff(x):
        w = _sigmoid(np.einsum("nmd,d->nm", A, x) * b)
        w = w * (1.0 - w)
        return np.einsum("nmr,nm,nms->nrs", AV, w, AV) / m

    def recon_mean(S):
        Y = np.einsum("ndr,nrs->nds", V[agg], S[agg])
        return (Y.transpose(1, 0, 2).reshape(d, n_agg * rb)
                @ V[agg].transpose(1, 0, 2).reshape(d, n_agg * rb).T) / n_agg

    eye = np.eye(d, dtype=dtype)
    x0 = np.zeros(d, dtype)
    L = coeff(x0)                                   # exact initial coefficients
    H = recon_mean(L) + lam_t * eye
    z = x0
    zs = [z]
    for _ in range(rounds):
        # the step solves against the estimate H^k learned so far; the
        # round's compressed coefficient differences then update it
        g = grads(A, b, lam_t, z)[agg].mean(axis=0)
        Hs = (H + H.T) / 2.0
        w, Q = np.linalg.eigh(Hs)
        P = Hs + (Q * (np.maximum(w, mu) - w)) @ Q.T      # [H^k]_μ
        x_next = z - np.linalg.solve(P, g)
        S = topk(coeff(z) - L, k)
        L = L + alpha * S
        H = H + recon_mean(alpha * S)
        if fault == "altered":
            x_next = x_next.copy()
            x_next[0] *= 1.001
        if fault != "frozen":
            z = z + eta * (x_next - z)
        zs.append(z)
    zs = np.stack(zs).astype(np.float64)
    gaps = np.array([loss(A, b, lam_t, zz.astype(dtype)) for zz in zs]) - f_star

    def legs(t):
        """Cumulative bits per node on each leg before rounds ``t``."""
        t = np.asarray(t, np.float64)
        return {
            "hess_up": rb * rb * FLOAT_BITS + t * min(k, rb * rb) * (FLOAT_BITS + INDEX_BITS),
            "grad_up": t * rb * FLOAT_BITS,
            "model_down": t * d * FLOAT_BITS,
            "basis_ship": np.full_like(t, d * rb * FLOAT_BITS),
        }

    return {"iterates": {t: zs[t] for t in (0, 1, rounds)}, "gaps": gaps, "legs": legs,
            "f_star": float(f_star)}
