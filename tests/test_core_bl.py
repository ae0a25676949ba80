"""System behaviour tests for BL1/BL2/BL3 and baselines against the paper's
claims: basis exactness, FedNL equivalence, superlinear local rates, and the
communication-cost ordering of Table 1 / Figures 1–2."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import baselines, bl, glm
from repro.core.basis import (
    PSDBasis,
    StandardBasis,
    SymmetricBasis,
    orth_basis_from_data,
)
from repro.core.compressors import Identity, RankR, TopK


@pytest.fixture(scope="module")
def problem():
    clients = glm.make_synthetic(seed=0, n_clients=8, m=50, d=60, r=20, lam=1e-3)
    x0 = jnp.zeros(60, jnp.float64)
    xs = glm.newton_solve(clients, x0, 20)
    return clients, x0, xs


# ------------------------------ bases --------------------------------------
@settings(max_examples=10, deadline=None)
@given(d=st.integers(3, 12), seed=st.integers(0, 50))
def test_basis_roundtrip_property(d, seed):
    A = jnp.asarray(np.random.default_rng(seed).standard_normal((d, d)))
    A = (A + A.T) / 2
    for B in [StandardBasis(d), SymmetricBasis(d), PSDBasis(d)]:
        np.testing.assert_allclose(
            np.asarray(B.reconstruct(B.h(A))), np.asarray(A), atol=1e-10
        )


def test_data_basis_exact_on_hessian(problem):
    clients, x0, _ = problem
    for c in clients[:3]:
        basis = orth_basis_from_data(c.A)
        Hd = glm.hess_data_part(c, x0)
        np.testing.assert_allclose(
            np.asarray(basis.reconstruct(basis.h(Hd))), np.asarray(Hd), atol=1e-9
        )
        # coefficient matrix is exactly r×r — everything else is 0 (Eq. 5)
        hmat = np.asarray(basis.h(Hd))
        assert np.abs(hmat[basis.r :, :]).max() == 0
        assert np.abs(hmat[:, basis.r :]).max() == 0


def test_psd_basis_matrices_are_psd():
    """Example 5.1's defining property, needed by BL3."""
    d = 5
    for j in range(d):
        for l in range(j + 1):
            B = np.zeros((d, d))
            if j == l:
                B[j, j] = 1
            else:
                B[j, l] = B[l, j] = B[j, j] = B[l, l] = 1
            assert np.linalg.eigvalsh(B).min() >= -1e-12


def test_psd_htilde_reconstruct_roundtrip():
    d = 7
    A = np.random.default_rng(0).standard_normal((d, d))
    A = (A + A.T) / 2
    M = bl._psd_h_tilde(jnp.asarray(A))
    back = bl._psd_reconstruct_full(M)
    np.testing.assert_allclose(np.asarray(back), A, atol=1e-10)


# ------------------------------ BL1 -----------------------------------------
def test_bl1_standard_basis_equals_fednl_shape(problem):
    """BL1 with the standard basis IS FedNL: h(A) = A, so the trajectory must
    match a direct FedNL implementation (here: BL1 where basis ops are
    identities) — we check self-consistency + convergence."""
    clients, x0, xs = problem
    n = len(clients)
    bases = [StandardBasis(60) for _ in range(n)]
    comp = [RankR(r=1) for _ in range(n)]
    h = bl.bl1(clients, bases, comp, Identity(), x0, xs, steps=25)
    assert h.gaps[-1] < 1e-8
    assert h.gaps[-1] < h.gaps[0]


def test_bl1_superlinear_local_rate(problem):
    """Theorem 4.10: with exact init near x*, the gap ratio must shrink."""
    clients, x0, xs = problem
    n = len(clients)
    bases = [orth_basis_from_data(c.A) for c in clients]
    comp = [TopK(k=b.r) for b in bases]
    h = bl.bl1(clients, bases, comp, Identity(), x0, xs, steps=14)
    g = np.asarray(h.gaps)
    g = g[g > 1e-13]
    ratios = g[1:] / g[:-1]
    # superlinear: contraction factors shrink once the Hessian estimate is
    # learned (ratios[0] is the initial exact-Newton jump; ratios[1] is the
    # compression-lagged worst case)
    assert np.min(ratios[2:]) < 0.25 * ratios[1] + 1e-12
    assert ratios[-1] < ratios[1]
    assert g[-1] < 1e-9


def test_bl1_beats_standard_basis_in_bits(problem):
    """The paper's core claim: same accuracy with far fewer bits when r≪d."""
    clients, x0, xs = problem
    n = len(clients)
    data_bases = [orth_basis_from_data(c.A) for c in clients]
    std_bases = [StandardBasis(60) for _ in range(n)]
    h_data = bl.bl1(clients, data_bases, [TopK(k=b.r) for b in data_bases],
                    Identity(), x0, xs, steps=20)
    h_std = bl.bl1(clients, std_bases, [RankR(r=1) for _ in range(n)],
                   Identity(), x0, xs, steps=20)

    def bits_to(h, tol):
        g = np.asarray(h.gaps)
        idx = np.argmax(g < tol)
        return h.up_bits[idx] if g[idx] < tol else np.inf

    assert bits_to(h_data, 1e-8) < bits_to(h_std, 1e-8)


def test_bl1_bidirectional_compression_converges(problem):
    clients, x0, xs = problem
    bases = [orth_basis_from_data(c.A) for c in clients]
    comp = [TopK(k=max(1, b.r // 2)) for b in bases]
    h = bl.bl1(clients, bases, comp, TopK(k=30), x0, xs, steps=40,
               alpha=1.0, eta=1.0, p=0.5, seed=3)
    assert h.gaps[-1] < 1e-6
    assert h.down_bits[-1] > 0  # backside compression active


# ------------------------------ BL2 / BL3 -----------------------------------
def test_bl2_full_participation_converges(problem):
    clients, x0, xs = problem
    bases = [orth_basis_from_data(c.A) for c in clients]
    h = bl.bl2(clients, bases, [TopK(k=b.r * 4) for b in bases],
               [Identity() for _ in clients], x0, xs, steps=25)
    assert h.gaps[-1] < 1e-7
    assert h.gaps[-1] < h.gaps[0] * 1e-4


def test_bl2_partial_participation_converges(problem):
    clients, x0, xs = problem
    bases = [orth_basis_from_data(c.A) for c in clients]
    h = bl.bl2(clients, bases, [TopK(k=b.r * 2) for b in bases],
               [Identity() for _ in clients], x0, xs, steps=40, tau=4, seed=2)
    assert h.gaps[-1] < 1e-6


def test_bl3_converges_and_beats_gd_in_bits(problem):
    clients, x0, xs = problem
    h3 = bl.bl3(clients, [TopK(k=120) for _ in clients],
                [Identity() for _ in clients], x0, xs, steps=60, option=2)
    assert h3.gaps[-1] < h3.gaps[0] * 1e-2
    hg = baselines.gd(clients, x0, xs, 200)
    # at equal bit budgets BL3 achieves a lower gap
    budget = h3.up_bits[-1]
    gd_idx = np.searchsorted(hg.up_bits, budget)
    gd_idx = min(gd_idx, len(hg.gaps) - 1)
    assert h3.gaps[-1] < hg.gaps[gd_idx]


def test_bl3_option1_converges(problem):
    clients, x0, xs = problem
    h = bl.bl3(clients, [TopK(k=300) for _ in clients],
               [Identity() for _ in clients], x0, xs, steps=40, option=1)
    assert h.gaps[-1] < h.gaps[0] * 1e-2


# ------------------------------ baselines -----------------------------------
def test_newton_basis_trajectory_identical(problem):
    """§A.4 / Table 1: the basis change is LOSSLESS — identical iterates at
    ~ (d²+d)/(r²+r) fewer floats per iteration."""
    clients, x0, xs = problem
    bases = [orth_basis_from_data(c.A) for c in clients]
    h1 = baselines.newton(clients, x0, xs, 6)
    h2 = baselines.newton(clients, x0, xs, 6, bases=bases)
    np.testing.assert_allclose(h1.gaps, h2.gaps, rtol=1e-5, atol=1e-12)
    per_iter_naive = h1.up_bits[2] - h1.up_bits[1]
    per_iter_basis = h2.up_bits[2] - h2.up_bits[1]
    d, r = 60, bases[0].r
    assert per_iter_naive / per_iter_basis == pytest.approx(
        (d * d + d) / (r * r + r), rel=1e-6
    )


def test_second_order_beats_first_order_in_bits(problem):
    """Fig. 1 row 2: BL1 beats GD/DIANA by orders of magnitude in bits."""
    clients, x0, xs = problem
    bases = [orth_basis_from_data(c.A) for c in clients]
    h_bl = bl.bl1(clients, bases, [TopK(k=b.r) for b in bases],
                  Identity(), x0, xs, steps=15)
    from repro.core.compressors import RandomDithering
    comp = RandomDithering(s=8)
    h_d = baselines.diana(clients, x0, xs, 150, comp, comp.omega_for(60))
    tol = 1e-6
    gb = np.asarray(h_bl.gaps)
    bl_bits = h_bl.up_bits[int(np.argmax(gb < tol))]
    gd_ = np.asarray(h_d.gaps)
    reached = gd_ < tol
    diana_bits = h_d.up_bits[int(np.argmax(reached))] if reached.any() else np.inf
    assert bl_bits * 5 < diana_bits  # ≥5× better (paper: orders of magnitude)


def test_nl1_converges(problem):
    clients, x0, xs = problem
    h = baselines.nl1(clients, x0, xs, steps=30, k=1)
    assert h.gaps[-1] < h.gaps[0] * 1e-3


def test_first_order_methods_monotone_decrease(problem):
    clients, x0, xs = problem
    for fn in [
        lambda: baselines.gd(clients, x0, xs, 30),
        lambda: baselines.local_gd(clients, x0, xs, 15),
    ]:
        h = fn()
        g = np.asarray(h.gaps)
        assert g[-1] < g[0]


def test_dore_like_bidirectional(problem):
    clients, x0, xs = problem
    h = baselines.dore_like(clients, x0, xs, 60, TopK(k=30), TopK(k=30))
    assert h.gaps[-1] < h.gaps[0]
    assert h.down_bits[-1] > 0


# ------------------------------ projection ----------------------------------
@settings(max_examples=10, deadline=None)
@given(d=st.integers(2, 10), seed=st.integers(0, 100))
def test_proj_mu_property(d, seed):
    A = jnp.asarray(np.random.default_rng(seed).standard_normal((d, d)))
    mu = 0.1
    P = bl.proj_mu(A, mu)
    w = np.linalg.eigvalsh(np.asarray(P))
    assert w.min() >= mu - 1e-9
    np.testing.assert_allclose(np.asarray(P), np.asarray(P).T, atol=1e-10)
    # idempotent on feasible matrices
    P2 = bl.proj_mu(P, mu)
    np.testing.assert_allclose(np.asarray(P2), np.asarray(P), atol=1e-9)


# ------------------------ certified μ-projection route ----------------------
_MU = 1e-3


def _sym_with_spectrum(d, seed, eigs):
    """Q diag(eigs) Qᵀ for a random orthogonal Q, off symmetry by rounding
    noise (the server's H is symmetric only up to its reductions' order)."""
    rng = np.random.default_rng(seed)
    Q, _ = np.linalg.qr(rng.standard_normal((d, d)))
    A = (Q * np.asarray(eigs)) @ Q.T
    return jnp.asarray(A + 1e-15 * rng.standard_normal((d, d))), jnp.asarray(
        rng.standard_normal(d))


def _route_solve(A, b, mu):
    solve = lambda P, *factors: bl.proj_mu_solve(P, *factors, b)
    return jax.jit(lambda A: bl.proj_mu_route(A, mu, solve))(A)


def _eigh_solve(A, b, mu):
    return jax.jit(lambda A: bl.proj_mu_solve(*bl.proj_mu_eig(A, mu), b))(A)


def _numpy_proj_solve(A, b, mu):
    S = (np.asarray(A) + np.asarray(A).T) / 2
    w, V = np.linalg.eigh(S)
    return V @ ((V.T @ np.asarray(b)) / np.maximum(w, mu))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("d", [8, 64])
def test_certified_route_matches_eigh_solve(d, seed):
    """sym(H) ⪰ μI with margin (fig1-xl's spectrum: λ in [3.5μ, 14μ]): the
    route skips the eigh, and its Cholesky solve agrees with the eigh
    solve to 1e-12."""
    A, b = _sym_with_spectrum(d, seed, np.linspace(3.5 * _MU, 14 * _MU, d))
    x, eigh = _route_solve(A, b, _MU)
    assert not bool(eigh)
    ref = _eigh_solve(A, b, _MU)
    assert np.linalg.norm(np.asarray(x - ref)) <= 1e-12 * np.linalg.norm(np.asarray(ref))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("d", [8, 64])
def test_route_takes_eigh_bitwise_where_projection_bites(d, seed):
    """μ above λ_min: the certificate fails, the route takes the eigh
    branch and returns bitwise what the eigh path returns."""
    A, b = _sym_with_spectrum(d, seed, np.linspace(0.1 * _MU, 14 * _MU, d))
    x, eigh = _route_solve(A, b, _MU)
    assert bool(eigh)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(_eigh_solve(A, b, _MU)))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("d", [8, 64])
def test_route_at_lambda_min_equal_mu(d, seed):
    """A rank-deficient data part plus the ridge μ: λ_min(H) is μ itself,
    and the certificate may go either way; the solve is within 1e-10 of
    the projected reference whichever it takes."""
    rng = np.random.default_rng(100 + seed)
    X = rng.standard_normal((d // 2, d))
    A = jnp.asarray(X.T @ X / d + _MU * np.eye(d))
    b = jnp.asarray(rng.standard_normal(d))
    x, _ = _route_solve(A, b, _MU)
    ref = _numpy_proj_solve(A, b, _MU)
    assert np.linalg.norm(np.asarray(x) - ref) <= 1e-10 * np.linalg.norm(ref)
