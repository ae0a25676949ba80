"""Generalized linear models for the paper's experiments (Eq. 16).

Regularized logistic regression:
    f(x) = (1/n) Σ_i f_i(x) + (λ/2)‖x‖²,
    f_i(x) = (1/m) Σ_j log(1 + exp(−b_ij a_ijᵀ x)).

We fold the ridge evenly into every client: f_i^λ(x) = f_i(x) + (λ/2)‖x‖², so
∇²f_i^λ = (1/m) Aᵀ D A + λI with D = diag(φ″).  Synthetic data generators
reproduce the LibSVM regimes of Table 2 (n clients, m points each, d features,
intrinsic dimension r ≪ d).
"""
from __future__ import annotations

import dataclasses
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.linalg import cho_solve, solve_triangular


@dataclasses.dataclass
class ClientData:
    A: jax.Array  # (m, d) features
    b: jax.Array  # (m,) labels in {−1, +1}
    lam: float    # ridge coefficient (shared)


def spd_solve(A: jax.Array, b: jax.Array) -> jax.Array:
    """A⁻¹b for symmetric positive definite A: a Cholesky factor and two
    triangular solves.  The Newton and BL2 server systems are SPD by
    construction (Hessians with a λI ridge, a symmetrized shift plus its
    ℓ-weighted identity), and the TPU compiler implements LU only in f32,
    so their f64 solves go through here ([·]_μ systems take
    `bl.proj_mu_solve`)."""
    L = jnp.linalg.cholesky(A)
    return cho_solve((L, True), b)


def qr_solve(A: jax.Array, b: jax.Array) -> jax.Array:
    """A⁻¹b for a square nonsingular A that need not be symmetric: QR and
    one triangular solve (compiles in f64 on TPU, unlike LU)."""
    Q, R = jnp.linalg.qr(A)
    return solve_triangular(R, Q.T @ b)


def sigmoid(t):
    return 0.5 * (jnp.tanh(t / 2.0) + 1.0)


def loss(data: ClientData, x: jax.Array) -> jax.Array:
    z = data.A @ x * data.b
    return jnp.mean(jnp.logaddexp(0.0, -z)) + 0.5 * data.lam * jnp.dot(x, x)


def grad(data: ClientData, x: jax.Array) -> jax.Array:
    z = data.A @ x * data.b
    coef = -data.b * sigmoid(-z)  # φ' = −b σ(−b aᵀx)
    return data.A.T @ coef / data.A.shape[0] + data.lam * x


def hess_diag_weights(data: ClientData, x: jax.Array) -> jax.Array:
    """φ″(a_jᵀx) for every sample: σ(z)(1−σ(z)) with z = b aᵀx (b²=1)."""
    z = data.A @ x * data.b
    s = sigmoid(z)
    return s * (1.0 - s)


def hess(data: ClientData, x: jax.Array) -> jax.Array:
    w = hess_diag_weights(data, x)
    m = data.A.shape[0]
    return (data.A * w[:, None]).T @ data.A / m + data.lam * jnp.eye(data.A.shape[1], dtype=x.dtype)


def hess_data_part(data: ClientData, x: jax.Array) -> jax.Array:
    """Hessian without the λI term (lives in the data subspace — §2.3)."""
    w = hess_diag_weights(data, x)
    m = data.A.shape[0]
    return (data.A * w[:, None]).T @ data.A / m


def global_loss(clients: List[ClientData], x: jax.Array) -> jax.Array:
    return jnp.mean(jnp.stack([loss(c, x) for c in clients]))


def global_grad(clients: List[ClientData], x: jax.Array) -> jax.Array:
    return jnp.mean(jnp.stack([grad(c, x) for c in clients]), axis=0)


def global_hess(clients: List[ClientData], x: jax.Array) -> jax.Array:
    return jnp.mean(jnp.stack([hess(c, x) for c in clients]), axis=0)


def newton_solve(clients: List[ClientData], x0: jax.Array, iters: int = 20) -> jax.Array:
    """Reference optimum: the paper uses the 20th Newton iterate as x*."""
    x = x0
    for _ in range(iters):
        g = global_grad(clients, x)
        Hm = global_hess(clients, x)
        x = x - spd_solve(Hm, g)
    return x


def make_synthetic(
    seed: int,
    n_clients: int,
    m: int,
    d: int,
    r: int,
    lam: float = 1e-3,
    noise: float = 0.1,
    heterogeneity: float = 0.5,
) -> List[ClientData]:
    """Low-intrinsic-dimension federated logistic regression data.

    Each client i draws an orthonormal subspace basis V_i ∈ R^{d×r} (shared
    global subspace rotated per-client by `heterogeneity` to model non-iid
    data), samples coefficients α ∈ R^{m×r}, sets A_i = α V_iᵀ (so rows live in
    an r-dim subspace exactly, as §2.3 assumes), and labels from a planted
    model with flip noise.
    """
    rng = np.random.default_rng(seed)
    Q_global, _ = np.linalg.qr(rng.standard_normal((d, r)))
    x_true = rng.standard_normal(d) / np.sqrt(d)
    clients = []
    for i in range(n_clients):
        P, _ = np.linalg.qr(
            (1 - heterogeneity) * Q_global + heterogeneity * rng.standard_normal((d, r))
        )
        alpha = rng.standard_normal((m, r))
        A = alpha @ P.T                      # rows ∈ span(P) exactly, rank ≤ r
        logits = A @ x_true
        p = 1.0 / (1.0 + np.exp(-logits))
        b = np.where(rng.random(m) < (1 - noise) * p + noise * 0.5, 1.0, -1.0)
        clients.append(
            ClientData(A=jnp.asarray(A, jnp.float64), b=jnp.asarray(b, jnp.float64), lam=lam)
        )
    return clients


# Table 2 regimes (scaled down ~ where needed so CPU tests stay fast)
TABLE2 = {
    "a1a": dict(n_clients=16, m=100, d=123, r=64),
    "phishing": dict(n_clients=10, m=11, d=68, r=35),
    "madelon-mini": dict(n_clients=10, m=40, d=200, r=60),
    "w2a-mini": dict(n_clients=10, m=69, d=300, r=59),
}


def make_table2(name: str, seed: int = 0, lam: float = 1e-3) -> List[ClientData]:
    return make_synthetic(seed=seed, lam=lam, **TABLE2[name])
