"""Every method the paper compares against (§6, §A) — same History contract.

Second order: Newton (naive / problem-structure / data-basis implementations,
§2.1–2.3 + §A.4 — the data-basis column communicates r²+r floats/iter per
Table 1's §2.3 block layout), NL1 [Islamov et al. 2021].  FedNL variants
come from `bl.bl1/bl2` with `StandardBasis`; FedNL-BAG below adds the
Bernoulli-aggregation follow-up (arXiv 2206.03588).

First order: GD, DIANA, ADIANA, Local-GD (S-Local-GD's p=q special case), and
a DORE-style bidirectionally-compressed GD with error feedback.  Gradient
compressors obey the same Eq. 6 (contractive) / Eq. 7 (unbiased) contracts
as the Hessian codecs.

Shared conventions: ``clients`` is a sequence of `glm.ClientData`; ``x0``
and ``x_star`` are (d,) arrays (x* the 20-iterate Newton reference
optimum); every function returns a `bl.History` of per-round gaps and
cumulative per-node uplink/downlink bits.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import glm
from .basis import MatrixBasis
from .bl import (
    _BACKENDS,
    History,
    _client_hcoef,
    _server_reconstruct,
    proj_mu_route,
    proj_mu_solve,
)
from .compressors import FLOAT_BITS, Compressor, RandK


def _fstar(clients, x_star):
    return float(glm.global_loss(list(clients), x_star))


def smoothness_constant(clients: Sequence[glm.ClientData]) -> float:
    """L = λ_max(∇²f) upper bound: logistic φ″ ≤ 1/4 ⇒ L ≤ ‖AᵀA‖/(4m) + λ."""
    Ls = []
    for c in clients:
        m = c.A.shape[0]
        s = jnp.linalg.norm(c.A, 2)
        Ls.append(float(s * s) / (4 * m) + c.lam)
    return max(Ls)


# --------------------------------------------------------------------------
# Newton implementations (Table 1's three columns)
# --------------------------------------------------------------------------
def newton(
    clients: Sequence[glm.ClientData],
    x0: jax.Array,
    x_star: jax.Array,
    steps: int,
    bases: Optional[Sequence[MatrixBasis]] = None,
    backend: str = "auto",
) -> History:
    """Classical Newton.  bases=None → naive d² floats/iter (§2.1);
    per-client DataOuterBasis → r²+r floats/iter (§2.3, the §A.4 comparison)."""
    if backend not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, got {backend!r}")
    if backend != "reference":
        from . import batched

        try:
            return batched.newton_fast(clients, x0, x_star, steps, bases=bases,
                                       sharded=(backend == "fast+sharded"))
        except batched.FastPathUnavailable:
            if backend != "auto":
                raise
    clients = list(clients)
    n = len(clients)
    d = x0.shape[0]
    lam = clients[0].lam
    f_star = _fstar(clients, x_star)
    x = x0
    up = 0.0
    if bases is not None:
        up = sum(float(b.d * b.r * FLOAT_BITS) for b in bases) / n  # ship bases once
    hist = History([], [], [])
    for _ in range(steps):
        hist.append(float(glm.global_loss(clients, x)) - f_star, up, 0.0)
        if bases is None:
            H = glm.global_hess(clients, x)
            g = glm.global_grad(clients, x)
            up += (d * d + d) * FLOAT_BITS
        else:
            # clients send Γ_i = V_iᵀ∇²f_i^data V_i (r² floats) + r grad coeffs
            H = sum(
                _server_reconstruct(bases[i], _client_hcoef(bases[i], clients[i], x), lam)
                for i in range(n)
            ) / n
            g = glm.global_grad(clients, x)
            up += sum(b.r * b.r + b.r for b in bases) / n * FLOAT_BITS
        x = x - glm.spd_solve(H, g)
    return hist


def fednl_bag(
    clients: Sequence[glm.ClientData],
    bases: Sequence[MatrixBasis],
    hess_comp: Sequence[Compressor],
    x0: jax.Array,
    x_star: jax.Array,
    steps: int,
    alpha: float = 1.0,
    q: float = 0.5,
    eta: Optional[float] = None,
    mu: Optional[float] = None,
    seed: int = 0,
    init_exact_hessian: bool = True,
    backend: str = "auto",
    exact: bool = True,
) -> History:
    """FedNL with Bernoulli-lazy gradient aggregation (BAG — after arXiv
    2206.03588): the FedNL compressed Hessian-learning recursion plus a
    gradient uplink where each client reports with probability q and the
    server lazily reuses the last reported gradient of silent clients.

    Spec-only method (`specs.FedNLBAGSpec` on the unified round engine);
    there is no op-by-op reference backend — tests pin it against a
    hand-rolled loop instead.
    """
    if backend not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, got {backend!r}")
    if backend == "reference":
        raise ValueError("fednl_bag is spec-only; no reference backend")
    from . import batched

    try:
        return batched.fednl_bag_fast(
            clients, bases, hess_comp, x0, x_star, steps, alpha=alpha, q=q,
            eta=eta, mu=mu, seed=seed, init_exact_hessian=init_exact_hessian,
            sharded=(backend == "fast+sharded"), exact=exact)
    except batched.FastPathUnavailable as e:
        # "auto" falls back to the reference loops everywhere else; with no
        # reference backend to fall back to, surface a clear error instead
        # of leaking the internal fallback signal
        raise ValueError(
            f"fednl_bag requires a stackable homogeneous fleet ({e})") from e


def nl1(
    clients: Sequence[glm.ClientData],
    x0: jax.Array,
    x_star: jax.Array,
    steps: int,
    k: int = 1,
    seed: int = 0,
) -> History:
    """NewtonLearn-1 [Islamov et al. 2021]: learn the m per-sample φ″
    coefficients with Rand-K (ω = m/K−1, α = 1/(ω+1)).  The server knows the
    training data (the method's stated privacy cost — Table 1)."""
    clients = list(clients)
    n = len(clients)
    d = x0.shape[0]
    lam = clients[0].lam
    f_star = _fstar(clients, x_star)
    key = jax.random.PRNGKey(seed)
    x = x0
    # h_i ∈ R^m learned coefficients, init at x0's true values
    hcoef = [glm.hess_diag_weights(c, x0) for c in clients]
    up = float(clients[0].A.shape[0] * FLOAT_BITS)  # ship h^0 (data assumed known)
    hist = History([], [], [])
    mu = lam

    def H_from(hc):
        total = jnp.zeros((d, d), x0.dtype)
        for i, c in enumerate(clients):
            m = c.A.shape[0]
            total = total + (c.A * hc[i][:, None]).T @ c.A / m
        return total / n + lam * jnp.eye(d, dtype=x0.dtype)

    for _ in range(steps):
        hist.append(float(glm.global_loss(clients, x)) - f_star, up, 0.0)
        g = glm.global_grad(clients, x)
        x = x - proj_mu_route(
            H_from(hcoef), mu, lambda P, *f: proj_mu_solve(P, *f, g))[0]
        step_bits = 0.0
        for i, c in enumerate(clients):
            m = c.A.shape[0]
            comp = RandK(k=k)
            alpha = 1.0 / (m / min(k, m))
            key, sk = jax.random.split(key)
            target = glm.hess_diag_weights(c, x)
            S, bits = comp(sk, target - hcoef[i])
            hcoef[i] = hcoef[i] + alpha * S
            step_bits += float(bits)
        up += step_bits / n + d * FLOAT_BITS  # gradients every step
    return hist


# --------------------------------------------------------------------------
# First-order methods
# --------------------------------------------------------------------------
def gd(clients, x0, x_star, steps, lr: Optional[float] = None,
       backend: str = "auto") -> History:
    """Distributed gradient descent; d floats/node/round uplink.

    Args:
      lr: step size (default 1/L via `smoothness_constant`).
      backend: "auto" | "fast" | "fast+sharded" | "reference".

    Returns a `History` (downlink is uncounted: exact broadcast).
    """
    if backend not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, got {backend!r}")
    if backend != "reference":
        from . import batched

        try:
            return batched.gd_fast(clients, x0, x_star, steps, lr=lr,
                                   sharded=(backend == "fast+sharded"))
        except batched.FastPathUnavailable:
            if backend != "auto":
                raise
    clients = list(clients)
    d = x0.shape[0]
    f_star = _fstar(clients, x_star)
    L = smoothness_constant(clients)
    lr = 1.0 / L if lr is None else lr
    x = x0
    up = 0.0
    hist = History([], [], [])
    for _ in range(steps):
        hist.append(float(glm.global_loss(clients, x)) - f_star, up, 0.0)
        x = x - lr * glm.global_grad(clients, x)
        up += d * FLOAT_BITS
    return hist


def diana(
    clients,
    x0,
    x_star,
    steps,
    comp: Compressor,
    omega: float,
    lr: Optional[float] = None,
    seed: int = 0,
    backend: str = "auto",
) -> History:
    """DIANA [Mishchenko et al. 2019]: compressed gradient differences with
    local shifts h_i; theoretical stepsizes.

    Args:
      comp: unbiased gradient compressor (Eq. 7), e.g. `RandomDithering`.
      omega: its variance parameter ω (e.g. ``comp.omega_for(d)``).
      lr: step size (default: the paper's theoretical
        min(α_h/2μ, 1/(L(1+6ω/n))) with α_h = 1/(ω+1)).
      seed: PRNG seed for the stochastic compressor draws.

    Returns a `History`; uplink bills the compressed difference messages.
    """
    if backend not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, got {backend!r}")
    if backend != "reference":
        from . import batched

        try:
            return batched.diana_fast(clients, x0, x_star, steps, comp, omega,
                                      lr=lr, seed=seed,
                                      sharded=(backend == "fast+sharded"))
        except batched.FastPathUnavailable:
            if backend != "auto":
                raise
    clients = list(clients)
    n = len(clients)
    d = x0.shape[0]
    f_star = _fstar(clients, x_star)
    L = smoothness_constant(clients)
    mu = clients[0].lam
    alpha_h = 1.0 / (omega + 1.0)
    lr = min(alpha_h / (2.0 * mu), 1.0 / (L * (1.0 + 6.0 * omega / n))) if lr is None else lr
    key = jax.random.PRNGKey(seed)
    x = x0
    h = [jnp.zeros(d, x0.dtype) for _ in range(n)]
    up = 0.0
    hist = History([], [], [])
    for _ in range(steps):
        hist.append(float(glm.global_loss(clients, x)) - f_star, up, 0.0)
        ghat = jnp.zeros(d, x0.dtype)
        step_bits = 0.0
        for i, c in enumerate(clients):
            key, sk = jax.random.split(key)
            gi = glm.grad(c, x)
            q, bits = comp(sk, gi - h[i])
            ghat = ghat + (h[i] + q) / n
            h[i] = h[i] + alpha_h * q
            step_bits += float(bits)
        x = x - lr * ghat
        up += step_bits / n
    return hist


def adiana(
    clients,
    x0,
    x_star,
    steps,
    comp: Compressor,
    omega: float,
    seed: int = 0,
) -> History:
    """ADIANA [Li et al. 2020, Alg. 1] with the paper's theoretical parameters
    (strongly convex case).

    Args as `diana` (no lr override — the accelerated stepsizes are coupled).
    Reference backend only (no spec/fast path).  Returns a `History`; each
    round bills TWO compressed messages per client (x^k and w^k shifts).
    """
    clients = list(clients)
    n = len(clients)
    d = x0.shape[0]
    f_star = _fstar(clients, x_star)
    L = smoothness_constant(clients)
    mu = clients[0].lam
    key = jax.random.PRNGKey(seed)

    alpha_h = 1.0 / (omega + 1.0)
    if omega == 0:
        eta = 1.0 / (2.0 * L)
    else:
        eta = min(1.0 / (2.0 * L), n / (64.0 * omega * L))
    theta1 = min(1.0 / 4.0, jnp.sqrt(eta * mu / 4.0).item())
    theta2 = 0.5
    gamma = eta / (2.0 * (theta1 + theta2 * eta * mu))
    beta = 1.0 - gamma * mu
    prob = theta2

    x = x0
    y = x0
    zv = x0
    wv = x0
    h = [jnp.zeros(d, x0.dtype) for _ in range(n)]
    h_avg = jnp.zeros(d, x0.dtype)
    up = 0.0
    hist = History([], [], [])
    for _ in range(steps):
        hist.append(float(glm.global_loss(clients, y)) - f_star, up, 0.0)
        xk = theta1 * zv + theta2 * wv + (1 - theta1 - theta2) * y
        ghat = h_avg
        step_bits = 0.0
        for i, c in enumerate(clients):
            key, sk = jax.random.split(key)
            gi = glm.grad(c, xk)
            q, bits = comp(sk, gi - h[i])
            ghat = ghat + q / n
            step_bits += float(bits)
            # shift update against w (ADIANA uses ∇f_i(w) differences)
        # update shifts toward ∇f_i(w^k)
        for i, c in enumerate(clients):
            key, sk = jax.random.split(key)
            gw = glm.grad(c, wv)
            qw, bits = comp(sk, gw - h[i])
            h_avg = h_avg + alpha_h * qw / n
            h[i] = h[i] + alpha_h * qw
            step_bits += float(bits)
        y_next = xk - eta * ghat
        zv = beta * zv + (1 - beta) * xk + (gamma / eta) * (y_next - xk)
        key, sk = jax.random.split(key)
        if bool(jax.random.bernoulli(sk, prob)):
            wv = y
        y = y_next
        up += step_bits / n
    return hist


def local_gd(clients, x0, x_star, steps, local_steps: int = 5, lr: Optional[float] = None) -> History:
    """Local GD (S-Local-GD's deterministic-sync special case): clients run
    `local_steps` gradient steps, then average — one d-float uplink per sync.

    Args:
      local_steps: local gradient steps between synchronizations.
      lr: local step size (default 1/L).

    Returns a `History` with one row per synchronization round.
    """
    clients = list(clients)
    n = len(clients)
    d = x0.shape[0]
    f_star = _fstar(clients, x_star)
    L = smoothness_constant(clients)
    lr = 1.0 / L if lr is None else lr
    x = x0
    up = 0.0
    hist = History([], [], [])
    for _ in range(steps):
        hist.append(float(glm.global_loss(clients, x)) - f_star, up, 0.0)
        locals_ = []
        for c in clients:
            xi = x
            for _ in range(local_steps):
                xi = xi - lr * glm.grad(c, xi)
            locals_.append(xi)
        x = sum(locals_) / n
        up += d * FLOAT_BITS
    return hist


def dore_like(
    clients,
    x0,
    x_star,
    steps,
    up_comp: Compressor,
    down_comp: Compressor,
    lr: Optional[float] = None,
    seed: int = 0,
) -> History:
    """DORE-style bidirectionally compressed GD with error feedback both ways.

    Args:
      up_comp / down_comp: uplink (per-client gradient) and downlink
        (model delta) compressors; error feedback accumulates what each
        round's compression dropped.
      lr: step size (default 0.5/L).
      seed: PRNG seed for stochastic compressors.

    Returns a `History`; the downlink stream is billed (unlike gd/diana).
    """
    clients = list(clients)
    n = len(clients)
    d = x0.shape[0]
    f_star = _fstar(clients, x_star)
    L = smoothness_constant(clients)
    lr = 0.5 / L if lr is None else lr
    key = jax.random.PRNGKey(seed)
    x = x0           # server model
    x_dev = x0       # device copy
    err_up = [jnp.zeros(d, x0.dtype) for _ in range(n)]
    err_down = jnp.zeros(d, x0.dtype)
    up = 0.0
    down = 0.0
    hist = History([], [], [])
    for _ in range(steps):
        hist.append(float(glm.global_loss(clients, x)) - f_star, up, down)
        agg = jnp.zeros(d, x0.dtype)
        sb = 0.0
        for i, c in enumerate(clients):
            key, sk = jax.random.split(key)
            gi = glm.grad(c, x_dev) + err_up[i]
            q, bits = up_comp(sk, gi)
            err_up[i] = gi - q
            agg = agg + q / n
            sb += float(bits)
        up += sb / n
        x = x - lr * agg
        key, sk = jax.random.split(key)
        delta = x - x_dev + err_down
        qd, dbits = down_comp(sk, delta)
        err_down = delta - qd
        down += float(dbits)
        x_dev = x_dev + qd
    return hist
