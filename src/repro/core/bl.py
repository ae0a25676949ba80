"""BL1 / BL2 / BL3 (Algorithms 1–3) — public API and backend dispatch.

Two backends implement the same algorithms with the same `History` contract:

  * ``repro.core.batched``      — the fast path: per-client state stacked on a
    leading axis, compressors vmapped, rounds run under `jax.lax.scan` inside
    one jitted XLA program.  Used whenever the configuration is homogeneous
    enough to stack (same client shapes, one basis kind, one compressor
    config per role).
  * ``repro.core.bl_reference`` — the original op-by-op Python loops, kept as
    the paper-faithful ground truth the fast path is pinned against.

`bl1/bl2/bl3` below take
``backend="auto"|"fast"|"fast+sharded"|"reference"``: "auto" (default) tries
the fast path and silently falls back, "fast" raises
`batched.FastPathUnavailable` instead of falling back, "fast+sharded" runs
the fast path with clients sharded over the mesh `data` axis (shard_map
aggregation backend — see `repro.core.rounds`), and "reference" forces the
loops.

Conventions
-----------
* Compression operates on *coefficient matrices* h^i(∇²f_i) in the client's
  basis.  With `DataOuterBasis` the Hessian's data part (which lives in the
  basis span) is encoded and the ridge λI is added analytically server-side,
  exactly as the paper's GLM experiments do; gradients likewise travel as r
  basis coefficients (§2.3, Table 1).
* `History` records per iteration: f(z)−f*, cumulative uplink bits/node and
  cumulative downlink bits/node (the paper plots uplink).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import glm
from .basis import DataOuterBasis, MatrixBasis, basis_transmission_bits
from .compressors import FLOAT_BITS, Compressor

_BACKENDS = ("auto", "fast", "fast+sharded", "reference")


def _sym_eigh(S: jax.Array):
    """(w, V) of a symmetric matrix.  A TPU takes the Jacobi algorithm: its
    default, QDWH spectral divide-and-conquer, does not compile in f64 at
    d=1200 within 15 minutes, Jacobi in about 30 seconds.  Every other
    platform keeps `jnp.linalg.eigh`."""
    def jacobi(S):
        V, w = jax.lax.linalg.eigh(
            S, implementation=jax.lax.linalg.EighImplementation.JACOBI)
        return w, V

    def default(S):
        w, V = jnp.linalg.eigh(S)
        return w, V

    return jax.lax.platform_dependent(S, tpu=jacobi, default=default)


def proj_mu_eig(A: jax.Array, mu: float):
    """[A]_μ, the projection onto {A = Aᵀ, A ⪰ μI}, with the
    eigendecomposition it comes from: ``(P, w_μ, V)`` with
    P = V diag(w_μ) Vᵀ and w_μ the eigenvalues of sym(A) clipped at μ.

    P is formed as sym(A) + V diag(w_μ − w) Vᵀ, the same matrix in exact
    arithmetic, so it is sym(A) itself wherever the projection does not
    bite, whatever the eigensolver's accuracy (a TPU's Jacobi eigh in
    emulated f64 is the less accurate one)."""
    S = (A + A.T) / 2.0
    w, V = _sym_eigh(S)
    w_mu = jnp.maximum(w, mu)
    return S + (V * (w_mu - w)) @ V.T, w_mu, V


def proj_mu_solve(P: jax.Array, w: jax.Array, V: jax.Array,
                  b: jax.Array) -> jax.Array:
    """P⁻¹b for ``(P, w, V) = proj_mu_eig(...)``: a solve through the
    eigendecomposition plus one step of iterative refinement against P.
    The server solves against [H]_μ (BL1, FedNL-BAG) take this form: the
    factors exist already, and on a TPU an f64 Cholesky (or QR) does not
    compile inside a client-sharded program, where this does."""
    x = V @ ((V.T @ b) / w)
    return x + V @ ((V.T @ (b - P @ x)) / w)


def proj_mu(A: jax.Array, mu: float) -> jax.Array:
    """[A]_μ: projection onto {A = Aᵀ, A ⪰ μI} (used by BL1)."""
    return proj_mu_eig(A, mu)[0]


def _sym(A):
    return (A + A.T) / 2.0


@dataclasses.dataclass
class History:
    gaps: List[float]
    up_bits: List[float]
    down_bits: List[float]
    #: optional per-leg cumulative bit streams keyed by `comm.CommLedger`
    #: leg name (hess_up / grad_up / model_down / basis_ship) — populated by
    #: the batched engine's ledger; the reference loops leave it None.
    legs: Optional[Dict[str, List[float]]] = None
    #: optional extra named evaluation streams beyond the gap (e.g. the
    #: BL-DNN spec's per-round training ``loss``) — whatever the method
    #: spec's ``eval_streams`` emitted besides ``"gap"``; None for GLM
    #: methods.
    metrics: Optional[Dict[str, List[float]]] = None
    #: optional per-round degradation-event bitmasks (`rounds.EVENT_*`,
    #: OR-combined ints) — populated by the service loop
    #: (`repro.launch.fed_serve`); the batch drivers leave it None.
    events: Optional[List[int]] = None

    def append(self, gap, up, down):
        self.gaps.append(float(max(gap, 0.0)))
        self.up_bits.append(float(up))
        self.down_bits.append(float(down))

    def as_arrays(self):
        return (np.asarray(self.gaps), np.asarray(self.up_bits), np.asarray(self.down_bits))


def _grad_uplink_bits(basis: MatrixBasis) -> float:
    return (basis.r if isinstance(basis, DataOuterBasis) else basis.d) * FLOAT_BITS


def _client_hcoef(basis: MatrixBasis, data: glm.ClientData, x: jax.Array) -> jax.Array:
    if isinstance(basis, DataOuterBasis):
        return basis.h(glm.hess_data_part(data, x))
    return basis.h(glm.hess(data, x))


def _server_reconstruct(basis: MatrixBasis, L: jax.Array, lam: float) -> jax.Array:
    H = basis.reconstruct(L)
    if isinstance(basis, DataOuterBasis):
        H = H + lam * jnp.eye(basis.d, dtype=H.dtype)
    return H


def _init_bits(basis: MatrixBasis, init_exact: bool) -> float:
    bits = basis_transmission_bits(basis)
    if init_exact:
        bits += basis.coeff_count() * FLOAT_BITS
    return bits


# --------------------------------------------------------------------------
# PSD-basis helpers shared by both BL3 backends (Example 5.1, §5)
# --------------------------------------------------------------------------
def _psd_sum_matrix(d: int, dtype) -> jax.Array:
    """Σ_{j,l} B^{jl} for the PSD basis (ordered pairs + diagonal)."""
    return 2.0 * jnp.ones((d, d), dtype) + (2.0 * d - 3.0) * jnp.eye(d, dtype=dtype)


def _psd_h_tilde(A: jax.Array) -> jax.Array:
    """h̃(A): symmetric coefficient matrix (halved off-diagonals) — §5."""
    off = (A - jnp.diag(jnp.diag(A))) / 2.0
    rowsum = jnp.sum(A, axis=1) - jnp.diag(A)
    return off + jnp.diag(jnp.diag(A) - rowsum)


def _psd_reconstruct_full(M: jax.Array) -> jax.Array:
    """Σ_{j,l} M_{jl} B^{jl} over all ordered pairs, for symmetric M."""
    off = M - jnp.diag(jnp.diag(M))
    diag = jnp.diag(M) + 2.0 * jnp.sum(off, axis=1)
    return 2.0 * off + jnp.diag(diag)


# --------------------------------------------------------------------------
# dispatchers
# --------------------------------------------------------------------------
def _dispatch(backend: str, fast_fn, ref_fn):
    """fast_fn takes sharded: bool (the aggregation backend of rounds.py)."""
    from .batched import FastPathUnavailable

    if backend not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, got {backend!r}")
    if backend == "reference":
        return ref_fn()
    try:
        return fast_fn(sharded=(backend == "fast+sharded"))
    except FastPathUnavailable:
        if backend == "auto":
            return ref_fn()
        raise


def bl1(
    clients: Sequence[glm.ClientData],
    bases: Sequence[MatrixBasis],
    hess_comp: Sequence[Compressor],
    model_comp: Compressor,
    x0: jax.Array,
    x_star: jax.Array,
    steps: int,
    alpha: float = 1.0,
    eta: float = 1.0,
    p: float = 1.0,
    mu: Optional[float] = None,
    seed: int = 0,
    init_exact_hessian: bool = True,
    backend: str = "auto",
    exact: bool = True,
    stream=None,
) -> History:
    """Basis Learn with Bidirectional Compression (Algorithm 1).

    StandardBasis + Rank-R + identity model compressor ≡ FedNL (option 1);
    Top-K model compressor ≡ FedNL-BC.

    Args:
      clients: n per-client GLM datasets (`glm.ClientData`).
      bases: one `MatrixBasis` per client (compression acts on the h^i(·)
        coefficient matrices in this basis — §2.3 / Eq. 10).
      hess_comp: one Hessian-coefficient compressor per client (contractive
        Eq. 6 with α=1, or unbiased Eq. 7 with α=1/(ω+1)).
      model_comp: single server→client model-stream compressor (Identity ⇒
        exact broadcast; Top-K ⇒ the bidirectional "BC" variants).
      x0: initial iterate, shape (d,).
      x_star: reference optimum (gap is f(z_t) − f(x_star)).
      steps: number of communication rounds.
      alpha: Hessian-learning step size of the shift recursion
        L ← L + αC(h(∇²f_i) − L).
      eta: model-stream step size z ← z + ηC(x − z).
      p: gradient-refresh probability (ξ ~ Bernoulli(p); p=1 ⇒ fresh
        gradients every round).
      mu: PSD-projection floor [·]_μ (defaults to the ridge λ).
      seed: PRNG seed for stochastic compressors / ξ draws.
      init_exact_hessian: ship exact initial coefficients (billed on the
        hess_up leg) instead of starting the learner at zero.
      backend: "auto" | "fast" | "fast+sharded" | "reference".
      exact: aggregation parity of the sharded backend (see
        `rounds.ShardMapReducer`): True (default) reduces via a fixed-order
        gather — bitwise identical to the single-device fast path; False
        uses ring collectives per the spec's `ReducePlan` — faster on real
        interconnects, reductions associate in ring order (≈ulp drift).
        Ignored off the "fast+sharded" backend.
      stream: optional `rounds.StreamHook` for mid-sweep progress emission
        (fast backends only; the reference loops ignore it).

    Returns:
      `History` — per-round gaps plus cumulative per-node uplink/downlink
      bits; `History.legs` carries the per-leg `CommLedger` streams on the
      fast backends.
    """
    from . import batched, bl_reference

    args = (clients, bases, hess_comp, model_comp, x0, x_star, steps)
    kw = dict(alpha=alpha, eta=eta, p=p, mu=mu, seed=seed,
              init_exact_hessian=init_exact_hessian)
    return _dispatch(
        backend,
        lambda sharded: batched.bl1_fast(*args, sharded=sharded, exact=exact,
                                         stream=stream, **kw),
        lambda: bl_reference.bl1_reference(*args, **kw),
    )


def bl2(
    clients: Sequence[glm.ClientData],
    bases: Sequence[MatrixBasis],
    hess_comp: Sequence[Compressor],
    model_comp: Sequence[Compressor],
    x0: jax.Array,
    x_star: jax.Array,
    steps: int,
    alpha: float = 1.0,
    eta: float = 1.0,
    p: float = 1.0,
    tau: Optional[int] = None,
    seed: int = 0,
    init_exact_hessian: bool = True,
    backend: str = "auto",
    exact: bool = True,
    stream=None,
) -> History:
    """Basis Learn with Bidirectional Compression and Partial Participation
    (Algorithm 2).  StandardBasis ≡ FedNL-PP (Rank-R, identity model comp).

    Args are as `bl1` except: `model_comp` is per-client (one compressor
    each, the downlink is client-individual z_i streams), `tau` is the
    expected participants per round (Bernoulli(τ/n) with a force-one-client
    fallback; defaults to full participation), and `p` is the per-client
    gradient-refresh probability (ξ_i masks, not the fleet-wide scalar).

    Returns a `History` (see `bl1`).
    """
    from . import batched, bl_reference

    args = (clients, bases, hess_comp, model_comp, x0, x_star, steps)
    kw = dict(alpha=alpha, eta=eta, p=p, tau=tau, seed=seed,
              init_exact_hessian=init_exact_hessian)
    return _dispatch(
        backend,
        lambda sharded: batched.bl2_fast(*args, sharded=sharded, exact=exact,
                                         stream=stream, **kw),
        lambda: bl_reference.bl2_reference(*args, **kw),
    )


def bl3(
    clients: Sequence[glm.ClientData],
    hess_comp: Sequence[Compressor],
    model_comp: Sequence[Compressor],
    x0: jax.Array,
    x_star: jax.Array,
    steps: int,
    alpha: float = 1.0,
    eta: float = 1.0,
    p: float = 1.0,
    tau: Optional[int] = None,
    c: float = 1e-8,
    option: int = 2,
    seed: int = 0,
    backend: str = "auto",
    exact: bool = True,
    stream=None,
) -> History:
    """BL3 with the PSD basis of Example 5.1 (both β options, Algorithm 3).

    Args are as `bl2` (no `bases` — the PSD basis is built in; no
    `init_exact_hessian` — BL3 always initializes at the exact h̃) plus:
    `c` is the γ_i floor (γ_i = max(c, max|L_i|)) and `option` selects the
    β_i candidate (1: previous-iterate numerator; 2: current target).

    Returns a `History` (see `bl1`).
    """
    from . import batched, bl_reference

    args = (clients, hess_comp, model_comp, x0, x_star, steps)
    kw = dict(alpha=alpha, eta=eta, p=p, tau=tau, c=c, option=option, seed=seed)
    return _dispatch(
        backend,
        lambda sharded: batched.bl3_fast(*args, sharded=sharded, exact=exact,
                                         stream=stream, **kw),
        lambda: bl_reference.bl3_reference(*args, **kw),
    )
