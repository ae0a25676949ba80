"""Plain NumPy reference of Basis Learn Algorithm 2 (partial
participation) on a host-resident federated fleet, independent of the
program under test.

It builds the fleet from the run's seed with its own copy of the
vectorised synthetic generator, and runs BL2 in the standard basis as the paper states
it, under the deployment's sampling: every ``rounds_per_cohort`` rounds a
cohort of ``cohort`` clients is drawn without replacement (Philox keyed on
the run's root key and the epoch: first distinct draws, or a permutation
when the cohort is over an eighth of the fleet), and in round t each cohort
member takes part with probability τ/n, drawn from the round's key folded
with the client's index (the smallest cohort index takes part when no one
does).  The server solves against the fleet means of every client's
(H_i, l_i, g_i), absent clients' state frozen at its last value; the ledger
counts each participant's Top-K message, gradient and model download over
the fleet size.  ``dtype`` runs the arithmetic in another precision (the
control); ``fault`` plants one of the faults the check has to catch.
"""
from __future__ import annotations

import numpy as np

FLOAT_BITS, INDEX_BITS = 64, 32
COHORT_SALT = 0x0C0407


def make_store(seed: int, n_clients: int, m: int, d: int, noise: float = 0.1):
    """(A (n, m, d), b (n, m)): full-rank rows, labels from a planted model
    with flip noise."""
    rng = np.random.default_rng(seed)
    x_true = rng.standard_normal(d) / np.sqrt(d)
    A = rng.standard_normal((n_clients, m, d)) / np.sqrt(d)
    p = 1.0 / (1.0 + np.exp(-(A @ x_true)))
    b = np.where(rng.random((n_clients, m)) < (1 - noise) * p + noise * 0.5, 1.0, -1.0)
    return A, b


def _sigmoid(t):
    return 0.5 * (np.tanh(t / 2.0) + 1.0)


def loss(A, b, lam, x, slab: int = 8192) -> float:
    tot = 0.0
    for lo in range(0, A.shape[0], slab):
        z = np.einsum("nmd,d->nm", A[lo:lo + slab], x) * b[lo:lo + slab]
        tot += float(np.sum(np.mean(np.logaddexp(0.0, -z), axis=1)))
    return tot / A.shape[0] + 0.5 * float(lam) * float(np.dot(x, x))


def _hess(A, b, lam, x):
    """Per-client full Hessians (k, d, d)."""
    w = _sigmoid(np.einsum("nmd,d->nm", A, x) * b)
    w = w * (1.0 - w)
    d = A.shape[2]
    return np.einsum("nmd,nm,nme->nde", A, w, A) / A.shape[1] + lam * np.eye(d, dtype=A.dtype)


def _grads(A, b, lam, x):
    """Per-client gradients (k, d)."""
    z = np.einsum("nmd,d->nm", A, x) * b
    return np.einsum("nmd,nm->nd", A, -b * _sigmoid(-z)) / A.shape[1] + lam * x


def newton_optimum(A, b, lam, iters: int = 30, tol: float = 1e-15, slab: int = 8192):
    n, m, d = A.shape
    x = np.zeros(d, A.dtype)
    for _ in range(iters):
        g = np.zeros(d, A.dtype)
        H = np.zeros((d, d), A.dtype)
        for lo in range(0, n, slab):
            g += _grads(A[lo:lo + slab], b[lo:lo + slab], 0.0, x).sum(axis=0)
            H += _hess(A[lo:lo + slab], b[lo:lo + slab], 0.0, x).sum(axis=0)
        step = np.linalg.solve(H / n + lam * np.eye(d, dtype=A.dtype), g / n + lam * x)
        x = x - step
        if np.linalg.norm(step) <= tol * max(1.0, np.linalg.norm(x)):
            break
    return x


def topk(delta, k: int):
    """Keep the k largest |entries| (compared in float32, ties to the
    earliest index) of one client's flattened matrix."""
    flat = delta.reshape(-1)
    if k >= flat.size:
        return delta.copy()
    order = np.argsort(-np.abs(flat).astype(np.float32), kind="stable")[:k]
    out = np.zeros_like(flat)
    out[order] = flat[order]
    return out.reshape(delta.shape)


class Sampler:
    """The deployment's two-level draw: cohorts per epoch, then per-round
    participation inside the cohort."""

    def __init__(self, seed: int, n: int, cohort: int, tau: int):
        import jax

        jax.config.update("jax_enable_x64", True)
        self.jax = jax
        # threefry is bit-exact on every backend; the host's keeps the chip free
        try:
            self.cpu = jax.devices("cpu")[0]
        except RuntimeError:
            self.cpu = jax.devices()[0]
        with jax.default_device(self.cpu):
            self.root = jax.random.PRNGKey(seed)
            kd = np.asarray(jax.random.key_data(jax.random.fold_in(self.root, COHORT_SALT)))
        kd = kd.astype(np.uint64).ravel()
        self.seed64 = (int(kd[0]) << 32) | int(kd[1])
        self.n, self.cohort, self.p = n, min(cohort, n), min(tau, n) / n

    def cohort_of(self, epoch: int) -> np.ndarray:
        n, c = self.n, self.cohort
        if c >= n:
            return np.arange(n, dtype=np.int64)
        rng = np.random.Generator(np.random.Philox(key=(self.seed64 << 64) + int(epoch)))
        if c * 8 <= n:
            chosen: list = []
            seen: set = set()
            while len(chosen) < c:
                for v in rng.integers(0, n, size=2 * c, dtype=np.int64).tolist():
                    if v not in seen:
                        seen.add(v)
                        chosen.append(v)
            idx = np.asarray(chosen[:c], np.int64)
        else:
            idx = rng.permutation(n)[:c]
        return np.sort(idx)

    def participants(self, t: int, cohort: np.ndarray) -> np.ndarray:
        jax, jr = self.jax, self.jax.random
        with jax.default_device(self.cpu):
            k_mask = jr.split(jr.split(jr.fold_in(self.root, t), 4)[0])[0]
            drawn = jax.vmap(lambda i: jr.bernoulli(jr.fold_in(k_mask, i), self.p, ()))(
                jax.numpy.asarray(cohort))
        part = cohort[np.asarray(drawn, bool)]
        return part if part.size else cohort[:1]


def run(seed: int, problem: dict, cell: dict, rounds: int, *,
        dtype=np.float64, fault: str | None = None) -> dict:
    """BL2 for ``rounds`` rounds; returns the server's iterates ``{0: x_0,
    1: x_1, rounds: x_rounds}`` (x_t is solved at the start of round t),
    the gaps f(x_t) − f* of every round, and the per-leg cumulative bits
    before each round.

    ``seed`` draws the fleet and is the serve loop's root PRNG seed, which
    draws the cohorts and the participants.

    ``fault``: None; ``"frozen"`` (participants' state is left unchanged);
    ``"half"`` (the server averages over the first half of the fleet);
    ``"altered"`` (the server's solve returns its answer scaled by 1.001).
    """
    n, m, d = problem["n_clients"], problem["m"], problem["d"]
    lam = dtype(problem["lam"])
    params = cell["params"]
    alpha, eta, p = params.get("alpha", 1.0), params.get("eta", 1.0), params.get("p", 1.0)
    if p != 1.0 or cell["model_comp"]["kind"] != "identity" \
            or cell["hess_comp"]["kind"] != "topk" or cell["basis"] != "standard":
        raise ValueError("the BL2 reference covers the standard basis, Top-K "
                         "Hessian compression, an identity downlink and p=1")
    k = cell["hess_comp"]["k"]
    tau, cohort, rpc = params["tau"], params["cohort"], params["rounds_per_cohort"]
    A, b = make_store(seed, n, m, d)
    A, b = A.astype(dtype), b.astype(dtype)
    f_star = loss(A, b, lam, newton_optimum(A, b, lam))
    n_agg = n // 2 if fault == "half" else n
    # x0 = 0: H_i = L_i = ∇²f_i(0), l_i = 0, g_i = −∇f_i(0), z_i = w_i = 0;
    # only the fleet sums are kept for clients not yet touched
    H_sum = np.zeros((d, d), dtype)
    g_sum = np.zeros(d, dtype)
    for lo in range(0, n_agg, 8192):
        hi = min(lo + 8192, n_agg)
        H_sum += _hess(A[lo:hi], b[lo:hi], lam, np.zeros(d, dtype)).sum(axis=0)
        g_sum -= _grads(A[lo:hi], b[lo:hi], lam, np.zeros(d, dtype)).sum(axis=0)
    l_sum = dtype(0.0)
    state: dict = {}
    sampler = Sampler(seed, n, cohort, tau)
    eye = np.eye(d, dtype=dtype)
    xs, counts = [], []
    for t in range(rounds + 1):
        H = H_sum / n_agg
        step = np.linalg.solve((H + H.T) / 2.0 + (l_sum / n_agg) * eye, g_sum / n_agg)
        x = 1.001 * step if fault == "altered" else step
        xs.append(x)
        if t == rounds:
            break
        part = sampler.participants(t, sampler.cohort_of(t // rpc))
        counts.append(part.size)
        if fault == "frozen":
            continue
        for i in part.tolist():
            Ai, bi = A[i:i + 1], b[i:i + 1]
            if i not in state:
                H0 = _hess(Ai, bi, lam, np.zeros(d, dtype))[0]
                state[i] = {"z": np.zeros(d, dtype), "L": H0, "H": H0,
                            "l": dtype(0.0), "g": -_grads(Ai, bi, lam, np.zeros(d, dtype))[0]}
            s = state[i]
            z = s["z"] + eta * (x - s["z"])
            target = _hess(Ai, bi, lam, z)[0]
            S = topk(target - s["L"], k)
            L = s["L"] + alpha * S
            Hn = s["H"] + alpha * S
            Hs = (Hn + Hn.T) / 2.0
            ln = np.sqrt(np.sum((Hs - target) ** 2))
            gn = Hs @ z + ln * z - _grads(Ai, bi, lam, z)[0]
            if i < n_agg:
                H_sum += Hn - s["H"]
                l_sum += ln - s["l"]
                g_sum += gn - s["g"]
            state[i] = {"z": z, "L": L, "H": Hn, "l": ln, "g": gn}
    xs = np.stack(xs).astype(np.float64)
    gaps = np.array([loss(A, b, lam, xx.astype(dtype)) for xx in xs]) - f_star

    def legs(t):
        """Cumulative bits per node on each leg before rounds ``t``."""
        t = np.asarray(t)
        while len(counts) < int(t.max(initial=0)):
            r = len(counts)
            counts.append(sampler.participants(r, sampler.cohort_of(r // rpc)).size)
        c = np.concatenate([[0], np.cumsum(counts)]).astype(np.float64)[t]
        return {
            "hess_up": d * d * FLOAT_BITS + c * min(k, d * d) * (FLOAT_BITS + INDEX_BITS) / n,
            "grad_up": c * d * FLOAT_BITS / n,
            "model_down": c * d * FLOAT_BITS / n,
            "basis_ship": np.zeros_like(c),
        }

    return {"iterates": {t: xs[t] for t in (0, 1, rounds)}, "gaps": gaps, "legs": legs,
            "f_star": float(f_star)}
