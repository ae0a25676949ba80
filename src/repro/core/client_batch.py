"""Stacked per-client state for the batched BL engine (`repro.core.batched`).

The op-by-op reference backend (`repro.core.bl_reference`) keeps clients as a
Python list and loops `for i in range(n)` every round.  The fast path instead
stacks everything into leading-axis-`n` device arrays:

  * `ClientBatch`  — data `A (n, m, d)`, labels `b (n, m)`, shared ridge λ;
  * `BatchedBasis` — one *kind* of `MatrixBasis` for the whole fleet, with
    per-client `DataOuterBasis` matrices zero-padded to a common `r_max`
    (`V (n, d, r_max)`; padded columns are exactly zero, so coefficients
    beyond a client's true rank are exactly zero — identical to the reference
    padding of r×r coefficients into a d×d array).

Both are registered JAX pytrees, so they flow through `jit`/`vmap`/`scan`
untouched.  The batched GLM math below mirrors `repro.core.glm` one-to-one
(same formulas, vectorized over the client axis), which is what makes the
fast-vs-reference parity tests in `tests/test_batched_parity.py` tight.

The hot coefficient transform Γ = VᵀAV can be routed through the batched
Pallas `basis_project` kernel (`repro.kernels.ops`) by setting
``REPRO_BL_PALLAS=1`` (interpreted on the CPU, compiled on a TPU); the
default is a float64 einsum, which the parity tests rely on (the kernel
computes in f32).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import glm
from .basis import (
    DataOuterBasis,
    DCTBasis,
    EigenBasis,
    MatrixBasis,
    PSDBasis,
    StandardBasis,
    SymmetricBasis,
)
from .comm import FLOAT_BITS


# --------------------------------------------------------------------------
# pytrees
# --------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ClientBatch:
    """All clients' GLM data stacked on a leading client axis."""

    A: jax.Array  # (n, m, d)
    b: jax.Array  # (n, m)
    lam: float    # shared ridge coefficient (static)

    def __post_init__(self):
        # runs on every pytree unflatten too (jit/scan/shard_map rebuild the
        # dataclass), so only validate when both leaves look like arrays —
        # tracers and ShapeDtypeStructs carry .shape/.ndim, placeholder
        # objects used by some tree utilities don't
        A, b = self.A, self.b
        if not (hasattr(A, "ndim") and hasattr(b, "ndim")):
            return
        if A.ndim != 3:
            raise ValueError(
                "ClientBatch.A must be client-stacked (n, m, d); got shape "
                f"{tuple(A.shape)}")
        if tuple(b.shape) != tuple(A.shape[:2]):
            raise ValueError(
                "ClientBatch.b must have shape (n, m) = A.shape[:2] = "
                f"{tuple(A.shape[:2])}; got {tuple(b.shape)} — a mis-shaped "
                "label array would silently broadcast into wrong per-client "
                "math")

    @property
    def n(self) -> int:
        return self.A.shape[0]

    @property
    def m(self) -> int:
        return self.A.shape[1]

    @property
    def d(self) -> int:
        return self.A.shape[2]

    def tree_flatten(self):
        return (self.A, self.b), (self.lam,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(A=children[0], b=children[1], lam=aux[0])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TreeBatch:
    """Client-stacked batch for arbitrary-pytree workloads (BL-DNN).

    The GLM engine's `ClientBatch` fixes the data layout to (A, b, λ); deep
    networks instead carry whatever pytree their loss consumes.  `data` is
    that pytree with every leaf stacked on a leading n_clients axis — the
    round engine shards it over `CLIENT_AXIS` exactly like `ClientBatch`
    (the shard_map in_spec is a per-leaf P(CLIENT_AXIS) prefix), and specs
    see the local (n_local, ...) slice.  `n_clients` is static so the
    driver can size reducers and meshes without touching device values.
    """

    data: object          # pytree; every leaf (n_clients, ...)
    n_clients: int        # static

    def __post_init__(self):
        # validate MUTUAL agreement of the stacked leaves' leading axis, not
        # agreement with the static n_clients: inside shard_map the leaves
        # are the (n_local, ...) shard while n_clients stays global, so a
        # check against n_clients would reject every sharded unflatten
        shaped = [leaf for leaf in jax.tree_util.tree_leaves(self.data)
                  if hasattr(leaf, "ndim")]
        if not shaped:
            return
        bad = [tuple(leaf.shape) for leaf in shaped if leaf.ndim < 1]
        if bad:
            raise ValueError(
                f"every TreeBatch leaf needs a leading client axis; got "
                f"scalar leaf shape(s) {bad}")
        leads = {leaf.shape[0] for leaf in shaped}
        if len(leads) > 1:
            raise ValueError(
                "TreeBatch leaves disagree on the leading client axis: got "
                f"sizes {sorted(leads, key=str)} across leaf shapes "
                f"{[tuple(leaf.shape) for leaf in shaped]}")

    @property
    def n(self) -> int:
        return self.n_clients

    def tree_flatten(self):
        return (self.data,), (self.n_clients,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(data=children[0], n_clients=aux[0])


def tree_batch(data, n_clients: Optional[int] = None) -> TreeBatch:
    """Build a `TreeBatch`, validating the shared leading client axis."""
    leaves = jax.tree_util.tree_leaves(data)
    if not leaves:
        raise ValueError("TreeBatch needs at least one data leaf")
    n = leaves[0].shape[0] if n_clients is None else n_clients
    for leaf in leaves:
        if leaf.ndim < 1 or leaf.shape[0] != n:
            raise ValueError(
                f"every TreeBatch leaf needs a leading n_clients={n} axis; "
                f"got shape {leaf.shape}")
    return TreeBatch(data=data, n_clients=int(n))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BatchedBasis:
    """A fleet-wide basis: one basis *kind*, per-client parameters stacked.

    kind ∈ {"standard", "symmetric", "psd", "data_outer", "eigen", "dct"}.
    For "data_outer", `V` is (n, d, r_max) with orthonormal columns up to
    each client's true rank and exact-zero padding beyond; `rs` keeps the
    true per-client ranks for bit accounting (the wire cost depends on r_i,
    not r_max).  For the rotation kinds ("eigen", "dct") every client uses
    the SAME orthogonal rotation (the eigenbasis of ∇²f(x⁰) is global by
    construction, the DCT is a convention) — `Q` is stored client-stacked
    (n, d, d) anyway so it shards over the client mesh exactly like `V`
    (the engine's shard_map in_specs are a per-leaf P(CLIENT_AXIS) prefix).
    """

    kind: str                   # static
    d: int                      # static
    rs: Tuple[int, ...]         # static: per-client ranks (d for non-data bases)
    V: Optional[jax.Array] = None  # (n, d, r_max) for kind == "data_outer"
    Q: Optional[jax.Array] = None  # (n, d, d) stacked rotation for eigen/dct

    @property
    def r_max(self) -> int:
        return max(self.rs)

    def tree_flatten(self):
        return (self.V, self.Q), (self.kind, self.d, self.rs)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(kind=aux[0], d=aux[1], rs=aux[2], V=children[0],
                   Q=children[1])

    # ---- bit accounting (host-side floats, no device sync) ----------------
    def grad_uplink_bits_mean(self) -> float:
        """Per-client gradient uplink cost, averaged over the fleet (§2.3:
        r_i coefficients for data bases, d floats otherwise)."""
        if self.kind == "data_outer":
            return sum(r * FLOAT_BITS for r in self.rs) / len(self.rs)
        return self.d * FLOAT_BITS

    def transmission_bits_mean(self) -> float:
        """One-time basis shipping cost averaged over clients (Table 1:
        rd floats for the data basis, d² for the learned eigenbasis; the
        convention bases — standard/symmetric/psd/dct — are free)."""
        if self.kind == "data_outer":
            return sum(self.d * r * FLOAT_BITS for r in self.rs) / len(self.rs)
        if self.kind == "eigen":
            return float(self.d * self.d * FLOAT_BITS)
        return 0.0

    def coeff_count_mean(self) -> float:
        if self.kind == "data_outer":
            return sum(r * r for r in self.rs) / len(self.rs)
        if self.kind in ("symmetric", "psd"):
            return self.d * (self.d + 1) / 2
        return self.d * self.d

    def init_coeff_bits_mean(self, init_exact: bool) -> float:
        """Bits for shipping the exact initial coefficients (hess-up leg);
        the one-time basis shipment is billed separately by the ledger."""
        return self.coeff_count_mean() * FLOAT_BITS if init_exact else 0.0

    # ---- coefficient transforms (batched h / reconstruct) -----------------
    def h(self, A: jax.Array) -> jax.Array:
        """Batched coefficient matrices: A (n, d, d) → (n, d, d)."""
        if self.kind == "standard":
            return A
        if self.kind == "symmetric":
            return jnp.tril(A)
        if self.kind == "psd":
            off = jnp.tril(A, -1)
            diag_v = jnp.diagonal(A, axis1=-2, axis2=-1)
            rowsum = jnp.sum(A, axis=-1) - diag_v
            eye = jnp.eye(self.d, dtype=A.dtype)
            return off + eye * (diag_v - rowsum)[..., :, None]
        if self.kind in ("eigen", "dct"):
            return jnp.einsum("ndr,nde,nes->nrs", self.Q, A, self.Q)
        gamma = _basis_project(self.V, A)            # (n, r_max, r_max)
        out = jnp.zeros(A.shape, A.dtype)
        return out.at[:, : self.r_max, : self.r_max].set(gamma)

    def reconstruct(self, H: jax.Array) -> jax.Array:
        """Batched Σ_{jl} H_{jl} B^{jl}: H (n, d, d) → (n, d, d)."""
        if self.kind == "standard":
            return H
        if self.kind == "symmetric":
            return jnp.tril(H) + jnp.transpose(jnp.tril(H, -1), (0, 2, 1))
        if self.kind == "psd":
            off = jnp.tril(H, -1)
            sym_off = off + jnp.transpose(off, (0, 2, 1))
            contrib = jnp.sum(sym_off, axis=-1)
            diag_v = jnp.diagonal(H, axis1=-2, axis2=-1) + contrib
            eye = jnp.eye(self.d, dtype=H.dtype)
            return sym_off + eye * diag_v[..., :, None]
        if self.kind in ("eigen", "dct"):
            return jnp.einsum("ndr,nrs,nes->nde", self.Q, H, self.Q)
        gamma = H[:, : self.r_max, : self.r_max]
        return jnp.einsum("ndr,nrs,nes->nde", self.V, gamma, self.V)

    def server_reconstruct(self, H: jax.Array, lam: float) -> jax.Array:
        """Reconstruct + analytic λI ridge for data bases (as the server does).
        Rotation/convention bases encode the FULL Hessian — no ridge."""
        out = self.reconstruct(H)
        if self.kind == "data_outer":
            out = out + lam * jnp.eye(self.d, dtype=out.dtype)
        return out


def _basis_project(V: jax.Array, A: jax.Array) -> jax.Array:
    """Γ = VᵀAV batched over clients: (n,d,r),(n,d,d) → (n,r,r).

    Routed through the Pallas `basis_project` kernel when REPRO_BL_PALLAS=1
    (accelerator deployments); einsum in float64 otherwise.
    """
    if os.environ.get("REPRO_BL_PALLAS", "0") == "1":
        from repro.kernels import ops

        return ops.basis_project(V, A)
    return jnp.einsum("ndr,nde,nes->nrs", V, A, V)


# --------------------------------------------------------------------------
# builders
# --------------------------------------------------------------------------
def from_clients(clients: Sequence[glm.ClientData]) -> Optional[ClientBatch]:
    """Stack a homogeneous client list; None if shapes/λ differ (fall back)."""
    clients = list(clients)
    if not clients:
        return None
    shape = clients[0].A.shape
    lam = clients[0].lam
    for c in clients:
        if c.A.shape != shape or c.b.shape != (shape[0],) or c.lam != lam:
            return None
    return ClientBatch(
        A=jnp.stack([c.A for c in clients]),
        b=jnp.stack([c.b for c in clients]),
        lam=lam,
    )


def stack_bases(bases: Sequence[MatrixBasis]) -> Optional[BatchedBasis]:
    """Stack a homogeneous-kind basis list; None if mixed kinds (fall back)."""
    bases = list(bases)
    if not bases:
        return None
    b0 = bases[0]
    for cls, kind in ((StandardBasis, "standard"), (SymmetricBasis, "symmetric"),
                      (PSDBasis, "psd")):
        if all(type(b) is cls for b in bases):
            if any(b.d != b0.d for b in bases):
                return None
            return BatchedBasis(kind=kind, d=b0.d, rs=tuple(b.d for b in bases))
    if all(type(b) is DCTBasis for b in bases):
        if any(b.d != b0.d for b in bases):
            return None
        return BatchedBasis(kind="dct", d=b0.d, rs=tuple(b.d for b in bases),
                            Q=jnp.stack([b.Q for b in bases]))
    if all(type(b) is EigenBasis for b in bases):
        # the eigenbasis is global by construction — require one shared Q
        # (heterogeneous rotations fall back to the reference loops)
        same = all(b.Q is b0.Q or np.array_equal(np.asarray(b.Q),
                                                 np.asarray(b0.Q))
                   for b in bases[1:])
        if any(b.d != b0.d for b in bases) or not same:
            return None
        return BatchedBasis(kind="eigen", d=b0.d, rs=tuple(b.d for b in bases),
                            Q=jnp.stack([b.Q for b in bases]))
    if all(type(b) is DataOuterBasis for b in bases):
        if any(b.d != b0.d for b in bases):
            return None
        rs = tuple(b.r for b in bases)
        r_max = max(rs)
        V = jnp.stack(
            [
                jnp.pad(b.V, ((0, 0), (0, r_max - b.r)))  # zero cols beyond r_i
                for b in bases
            ]
        )
        return BatchedBasis(kind="data_outer", d=b0.d, rs=rs, V=V)
    return None


# --------------------------------------------------------------------------
# host-resident client store (cohort streaming)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ClientStore:
    """The full fleet's data and per-client carry state, host-resident.

    The stacked engine puts all n clients on device, which bounds n by HBM
    (fig1-xl tops out at 512 clients).  The cohort-streaming engine
    (`repro.core.cohort`) instead keeps the fleet here — numpy arrays in
    host RAM — and per epoch gathers only the sampled cohort's rows onto
    the device.  `state` holds the client-stacked carry leaves (shifts
    z_i/w_i, Hessian estimates, ...) between the rounds a client is
    sampled; per Alg. 2–3 an absent client's state stays frozen, which is
    exactly what "rows not gathered this epoch don't move" gives us.

    NOT a pytree on purpose: the store never crosses the jit boundary —
    only gathered cohorts do.
    """

    A: np.ndarray             # (n, m, d) float64, host
    b: np.ndarray             # (n, m) float64, host
    lam: float
    state: dict = dataclasses.field(default_factory=dict)  # name -> (n, ...)

    @property
    def n(self) -> int:
        return self.A.shape[0]

    @property
    def m(self) -> int:
        return self.A.shape[1]

    @property
    def d(self) -> int:
        return self.A.shape[2]

    # ---- data plane -------------------------------------------------------
    def gather_data(self, idx: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side cohort gather: (A[idx], b[idx]) as fresh numpy arrays.
        Split from `gather_batch` so the prefetch thread can do the O(c·m·d)
        copy (and the H2D transfer) off the critical path."""
        return self.A[idx], self.b[idx]

    def gather_batch(self, idx: np.ndarray) -> ClientBatch:
        """Materialize the cohort's `ClientBatch` on device."""
        A, b = self.gather_data(idx)
        return ClientBatch(A=jnp.asarray(A), b=jnp.asarray(b), lam=self.lam)

    # ---- state plane ------------------------------------------------------
    def gather_state(self, idx: np.ndarray) -> dict:
        """Cohort rows of every carry leaf (fresh arrays, safe to mutate)."""
        return {name: leaf[idx] for name, leaf in self.state.items()}

    def scatter_state(self, idx: np.ndarray, updates: dict) -> None:
        """Write a cohort's updated carry rows back into the fleet store."""
        for name, rows in updates.items():
            self.state[name][idx] = rows

    def state_sums(self, names: Sequence[str]) -> dict:
        """Float64 fleet-wide sums of the named leaves (O(n), used once at
        init to seed the incrementally-maintained aggregate totals)."""
        return {name: np.sum(np.asarray(self.state[name], np.float64), axis=0)
                for name in names}


def synthetic_store(seed: int, n_clients: int, m: int, d: int,
                    lam: float = 1e-3, noise: float = 0.1) -> ClientStore:
    """Vectorized synthetic logistic-regression fleet for the streaming
    engine — same planted-model-with-flip-noise label scheme as
    `glm.make_synthetic`, but built in one shot with no per-client Python
    loop (the stacked builder's per-client QR is infeasible at n ≥ 100k).
    Rows are full-rank (the stream path runs the standard basis, so §2.3's
    low-rank row structure buys nothing here)."""
    rng = np.random.default_rng(seed)
    x_true = rng.standard_normal(d) / np.sqrt(d)
    A = rng.standard_normal((n_clients, m, d)) / np.sqrt(d)
    logits = A @ x_true
    p = 1.0 / (1.0 + np.exp(-logits))
    b = np.where(rng.random((n_clients, m)) < (1 - noise) * p + noise * 0.5,
                 1.0, -1.0)
    return ClientStore(A=np.asarray(A, np.float64),
                       b=np.asarray(b, np.float64), lam=lam)


# --------------------------------------------------------------------------
# batched GLM math (mirrors repro.core.glm, vectorized over clients)
# --------------------------------------------------------------------------
def bmv(M: jax.Array, v: jax.Array) -> jax.Array:
    """Per-client matvec (n, k, e) @ (n, e) → (n, k) as multiply+reduce
    (rank-3 M only — the broadcast inserts exactly one middle axis).

    `jnp.einsum("n...e,ne->n...")` lowers to a batched dot whose accumulation
    order depends on the leading batch size, so per-client results differ in
    the last ulp between a 1-client shard and an n-client stack — breaking
    the sharded aggregation backend's bitwise-parity contract
    (tests/test_sharding_multidev.py).  The multiply+last-axis-reduce form is
    batch-size invariant and cheap next to the engine's matrix-matrix
    contractions (which XLA compiles batch-invariantly already)."""
    return jnp.sum(M * v[:, None, :], axis=-1)


def _per_client_x(batch: ClientBatch, x: jax.Array) -> jax.Array:
    """Broadcast a shared iterate (d,) to (n, d); pass (n, d) through."""
    if x.ndim == 1:
        return jnp.broadcast_to(x, (batch.n, batch.d))
    return x


def losses(batch: ClientBatch, x: jax.Array) -> jax.Array:
    xb = _per_client_x(batch, x)
    z = bmv(batch.A, xb) * batch.b
    data = jnp.mean(jnp.logaddexp(0.0, -z), axis=1)
    return data + 0.5 * batch.lam * jnp.sum(xb * xb, axis=1)


def global_loss(batch: ClientBatch, x: jax.Array) -> jax.Array:
    return jnp.mean(losses(batch, x))


@jax.named_scope("oracle")
def grads(batch: ClientBatch, x: jax.Array) -> jax.Array:
    """Per-client gradients (n, d) at a shared or per-client iterate."""
    xb = _per_client_x(batch, x)
    z = bmv(batch.A, xb) * batch.b
    coef = -batch.b * glm.sigmoid(-z)
    return jnp.einsum("nmd,nm->nd", batch.A, coef) / batch.m + batch.lam * xb


def global_grad(batch: ClientBatch, x: jax.Array) -> jax.Array:
    return jnp.mean(grads(batch, x), axis=0)


def hess_weights(batch: ClientBatch, x: jax.Array) -> jax.Array:
    xb = _per_client_x(batch, x)
    z = bmv(batch.A, xb) * batch.b
    s = glm.sigmoid(z)
    return s * (1.0 - s)


def hess_data_part(batch: ClientBatch, x: jax.Array) -> jax.Array:
    """Per-client data-part Hessians (n, d, d) — no λI term (§2.3)."""
    w = hess_weights(batch, x)
    return jnp.einsum("nmd,nm,nme->nde", batch.A, w, batch.A) / batch.m


@jax.named_scope("oracle")
def hess(batch: ClientBatch, x: jax.Array) -> jax.Array:
    """Per-client full Hessians (n, d, d)."""
    H = hess_data_part(batch, x)
    return H + batch.lam * jnp.eye(batch.d, dtype=H.dtype)


def global_hess(batch: ClientBatch, x: jax.Array) -> jax.Array:
    return jnp.mean(hess(batch, x), axis=0)


def global_hess_fused(batch: ClientBatch, x: jax.Array) -> jax.Array:
    """Global Hessian ∇²f(x) = mean_i ∇²f_i(x) WITHOUT the (n, d, d)
    per-client intermediate: one (n·m, d)-shaped weighted Gram contraction.

    At `repro.exp`'s fig1-xl scale (n=512, d=1200) the stacked per-client
    Hessians alone are ~5.9 GB f64; this form never materializes them.
    Accumulation order differs from `global_hess` (contract over n·m at
    once vs per-client then mean), so results agree to f64 roundoff, not
    bitwise — use it for solver/reference-optimum work, not inside the
    parity-pinned round engine."""
    w = hess_weights(batch, x)                      # (n, m)
    Aw = batch.A * w[..., None]                     # (n, m, d)
    H = jnp.einsum("nmd,nme->de", Aw, batch.A) / (batch.n * batch.m)
    return H + batch.lam * jnp.eye(batch.d, dtype=H.dtype)


def newton_solve_fused(batch: ClientBatch, x0: jax.Array,
                       iters: int = 20) -> jax.Array:
    """Reference optimum x* by full Newton on the stacked fleet, using the
    low-memory `global_hess_fused` contraction each iteration.

    The scale-friendly analogue of `glm.newton_solve` (which loops clients
    in Python and stacks (n, d, d) Hessians) — same algorithm, fused math.
    """
    x = x0
    for _ in range(iters):
        x = _newton_step_fused(batch, x)
    return x


@jax.jit
def _newton_step_fused(batch: ClientBatch, x: jax.Array) -> jax.Array:
    # the fleet is an argument, not a closure: a closed-over fleet is
    # embedded in the executable as a constant (1.1 GB at fig1-xl scale)
    g = global_grad(batch, x)
    H = global_hess_fused(batch, x)
    return x - glm.spd_solve(H, g)


def hess_coeff_target(basisb: BatchedBasis, batch: ClientBatch, x: jax.Array) -> jax.Array:
    """Batched h^i(∇²f_i): data bases see only the data part (ridge is added
    analytically server-side), dense bases see the full Hessian — exactly
    `bl._client_hcoef` vectorized."""
    if basisb.kind == "data_outer":
        return basisb.h(hess_data_part(batch, x))
    return basisb.h(hess(batch, x))


# --------------------------------------------------------------------------
# r-dim coordinate-space fast path (§2.3): never materialize the d×d Hessian
# --------------------------------------------------------------------------
def basis_AV(basisb: BatchedBasis, batch: ClientBatch) -> jax.Array:
    """Per-client data matrices pre-rotated into the basis: (n, m, r_max).

    Computed once per run; with it the coefficient target collapses to an
    r-dim quadratic form (`hess_coeff_block`)."""
    return jnp.einsum("nmd,ndr->nmr", batch.A, basisb.V)


def hess_coeff_block(basisb: BatchedBasis, batch: ClientBatch, x: jax.Array,
                     AV: jax.Array) -> jax.Array:
    """Γ_i = Vᵢᵀ(∇²f_i^data)Vᵢ = (AᵢVᵢ)ᵀ Dᵢ (AᵢVᵢ)/m, natively (n, r, r).

    Same math as `hess_coeff_target` for the data basis, but O(n·m·r²)
    instead of O(n·m·d²) and no (n, d, d) intermediate — the batched
    engine's block mode keeps coefficient state in this compact form."""
    w = hess_weights(batch, x)
    return jnp.einsum("nmr,nm,nms->nrs", AV, w, AV) / batch.m


def reconstruct_block(basisb: BatchedBasis, G: jax.Array) -> jax.Array:
    """(n, r, r) block coefficients → (n, d, d) data-part Hessians."""
    return jnp.einsum("ndr,nrs,nes->nde", basisb.V, G, basisb.V)
