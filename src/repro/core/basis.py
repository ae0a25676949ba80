"""Basis Learn: changes of basis in R^{d×d} and S^d (paper §2.3, §4, §5).

A `MatrixBasis` provides the coefficient transform h(A) (forward) and the
reconstruction A = Σ_{jl} h_{jl} B^{jl} (backward).  All transforms are exact
(lossless); lossy compression is applied to the *coefficient matrix* by the
algorithms.

The module doubles as a **basis registry** — "which basis" is the system's
primary configuration axis (the paper's thesis is that the basis, not the
compressor, is the big lever on communication), so bases are registered
under string names and built per-fleet with `make_bases(name, clients,
...)`:

  * ``standard``    — Example 4.1 (h(A) = A); N_B orthogonal.
  * ``symmetric``   — Example 4.2 (triangular coefficients for S^d).
  * ``psd``         — Example 5.1 (B^{jl} ⪰ 0, for BL3).
  * ``data_outer``  — §2.3: client data spans G_i = span{v_1..v_r}; the
                      coefficient matrix of any A = Σ γ_tl v_t v_l^T is the
                      r×r matrix Γ.  h(A) is computed in the r-dim
                      coordinate space (Γ = pinv-projection), NEVER via the
                      d²×d² inverse.
  * ``eigen``       — eigenbasis of the initial averaged Hessian ∇²f(x⁰):
                      B^{jl} = q_j q_lᵀ for Q the orthonormal eigenvectors.
                      Concentrates coefficient energy on the leading
                      curvature directions; shipped once (d² floats, billed
                      on the ledger's basis leg).
  * ``dct``         — fixed orthogonal DCT-II basis: same rotation machinery
                      as ``eigen`` but *conventional* — both sides generate
                      it, zero shipment cost.
  * ``per_layer_svd`` — the *pytree* basis (BL-DNN): per-2-D-weight complete
                      SVD rotations of a parameter tree's initialization,
                      shipped once like ``eigen``.  Registered with
                      ``pytree=True`` — it transforms parameter pytrees,
                      not d×d matrices (see `PerLayerSVDBasis`).
  * ``dct_tree``, ``hadamard_tree`` — free *structured* pytree bases
                      (`StructuredTreeBasis`): per-leaf DCT-II /
                      Walsh–Hadamard rotations generated from leaf shapes
                      by both sides — the same rotation machinery as
                      ``per_layer_svd`` at zero shipment cost.

Shipped bases (``eigen``'s Q, ``per_layer_svd``'s leaf factors) can travel
COMPRESSED: `quantize_ship_factor` applies a `comm.BasisShipSpec` (bf16 /
int8 quantization, top-|·| column sparsification) to the factors the
receiver actually rotates with, and prices the shipment through the same
`comm.price` algebra as every other leg.

For DataOuterBasis, coefficient matrices are r×r embedded in the top-left of
a d×d array padded with exact zeros, so the same compressor machinery
applies and the bit accountant only ever "sees" r² potentially-nonzero
coefficients.

New bases register with `@register_basis("name")` and are automatically
picked up by the benchmark grid (`benchmarks/run.py::basis_matrix`) and the
round-trip contract tests (tests/test_basis_registry.py).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import comm
from .comm import FLOAT_BITS


class MatrixBasis:
    d: int
    #: number of (potentially) nonzero coefficients for a symmetric input
    n_coeff: int
    #: orthogonal basis (N_B = 1 in Eq. 10) ?
    orthogonal: bool = False
    #: max_jl ||B^jl||_F  (R in Assumption 4.7)
    R: float = 1.0
    #: all basis matrices PSD (required by BL3)?
    psd: bool = False

    def h(self, A: jax.Array) -> jax.Array:
        """Coefficient matrix of A (Eq. 10 forward transform).

        Args:  A — (d, d) matrix (symmetric for the S^d bases).
        Returns: (d, d) coefficient array; exact zeros where the basis
        stores nothing (e.g. outside the top-left r×r block for
        `DataOuterBasis`), so the bit accountant only "sees" the
        potentially-nonzero coefficients.
        """
        raise NotImplementedError

    def reconstruct(self, H: jax.Array) -> jax.Array:
        """Backward transform Σ_{jl} H_{jl} B^{jl}: (d, d) coefficients →
        (d, d) matrix.  Exact inverse of `h` on the basis span."""
        raise NotImplementedError

    def coeff_count(self) -> int:
        """Number of potentially-nonzero coefficients for a symmetric
        input (what a dense uplink of h(A) would transmit)."""
        return self.n_coeff


@dataclasses.dataclass
class StandardBasis(MatrixBasis):
    """Example 4.1: B^{jl} = e_j e_l^T.  h(A) = A.  BL1 ≡ FedNL here."""
    d: int

    def __post_init__(self):
        self.n_coeff = self.d * self.d
        self.orthogonal = True
        self.R = 1.0

    def h(self, A):
        return A

    def reconstruct(self, H):
        return H


@dataclasses.dataclass
class SymmetricBasis(MatrixBasis):
    """Example 4.2 specialized to symmetric A: h(A) = lower-triangular part.

    B^{jl} (j>l) has 1 at (j,l) and (l,j); B^{jj} has 1 at (j,j).
    Reconstruction of a lower-triangular coefficient matrix gives back A.
    """
    d: int

    def __post_init__(self):
        self.n_coeff = self.d * (self.d + 1) // 2
        self.orthogonal = True  # the B^{jl} are mutually orthogonal in <.,.>_F
        self.R = float(np.sqrt(2.0))

    def h(self, A):
        return jnp.tril(A)

    def reconstruct(self, H):
        return jnp.tril(H) + jnp.tril(H, -1).T


@dataclasses.dataclass
class PSDBasis(MatrixBasis):
    """Example 5.1: for j≠l, B^{jl} has ones at (j,l),(l,j),(j,j),(l,l) — PSD.

    For a symmetric A with coefficients c_{jl} (j≥l):
        A_{jl} = c_{jl}                (j≠l)
        A_{jj} = c_{jj} + Σ_{l≠j} c_{max(j,l),min(j,l)}
    so  h: c_{jl} = A_{jl} (j>l),  c_{jj} = A_{jj} − Σ_{l≠j} A_{jl}.
    Not orthogonal (N_B = d² in Eq. 10).  R = 2 (‖B^{jl}‖_F = 2 for j≠l).
    """
    d: int

    def __post_init__(self):
        self.n_coeff = self.d * (self.d + 1) // 2
        self.orthogonal = False
        self.R = 2.0
        self.psd = True

    def h(self, A):
        off = jnp.tril(A, -1)
        rowsum = jnp.sum(A, axis=1) - jnp.diag(A)  # Σ_{l≠j} A_{jl}
        diag = jnp.diag(A) - rowsum
        return off + jnp.diag(diag)

    def reconstruct(self, H):
        # H lower-triangular coefficient matrix
        off = jnp.tril(H, -1)
        sym_off = off + off.T
        contrib = jnp.sum(sym_off, axis=1)         # Σ_{l≠j} c_.. landing on (j,j)
        diag = jnp.diag(H) + contrib
        return sym_off + jnp.diag(diag)


@dataclasses.dataclass
class DataOuterBasis(MatrixBasis):
    """§2.3 data-induced basis: {v_t v_l^T}_{t,l∈[r]} completed arbitrarily.

    V ∈ R^{d×r} has orthonormal columns spanning the client's data subspace
    (scipy.linalg.orth analogue, computed with jnp SVD).  For any A in the span
    (all GLM Hessians minus the λI ridge term are),  Γ = Vᵀ A V  and
    A = V Γ Vᵀ exactly.  Coefficients live in the top-left r×r block.

    The ridge term λI is handled *analytically* by the algorithms (the server
    knows λ), exactly as the paper's experiments do — only the data part of the
    Hessian is ever communicated.
    """
    V: jax.Array  # (d, r), orthonormal columns

    def __post_init__(self):
        self.d = int(self.V.shape[0])
        self.r = int(self.V.shape[1])
        self.n_coeff = self.r * self.r
        self.orthogonal = True  # orthonormal v ⇒ <v_t v_l^T, v_p v_q^T>_F = δ
        self.R = 1.0

    def h(self, A):
        gamma = self.V.T @ A @ self.V
        out = jnp.zeros((self.d, self.d), A.dtype)
        return out.at[: self.r, : self.r].set(gamma)

    def reconstruct(self, H):
        gamma = H[: self.r, : self.r]
        return self.V @ gamma @ self.V.T


@dataclasses.dataclass
class RotationBasis(MatrixBasis):
    """B^{jl} = q_j q_lᵀ for one orthogonal Q ∈ R^{d×d}: a complete
    orthonormal basis of R^{d×d}, so h(A) = QᵀAQ and A = Q h Qᵀ exactly for
    EVERY matrix (no data-span assumption, no analytic ridge)."""
    Q: jax.Array  # (d, d), orthogonal

    def __post_init__(self):
        self.d = int(self.Q.shape[0])
        self.n_coeff = self.d * self.d
        self.orthogonal = True
        self.R = 1.0

    def h(self, A):
        return self.Q.T @ A @ self.Q

    def reconstruct(self, H):
        return self.Q @ H @ self.Q.T


@dataclasses.dataclass
class EigenBasis(RotationBasis):
    """Eigenbasis of the initial averaged Hessian ∇²f(x⁰) (the "basis
    matters" demonstration basis): curvature concentrates coefficient energy
    in the leading eigendirections, so Top-K in this basis keeps more signal
    per bit than the standard basis.  Q is NOT a convention — it depends on
    the fleet's data — so it ships once (d² floats, `basis_transmission_bits`)
    and the comm ledger bills it on the ``basis_ship`` leg."""

    def shipped(self, ship: comm.BasisShipSpec
                ) -> Tuple["EigenBasis", float]:
        """The basis as it arrives after a compressed shipment: Q quantized
        per `ship`, plus the exact bits that shipment cost (priced through
        `comm.price` on the shipment wire).  The receiver rotates with the
        QUANTIZED Q — a narrow wire trades reconstruction fidelity for
        bits, and both sides of that trade are observable (the bf16
        envelope is pinned in tests/test_basis_registry.py)."""
        Q, bits = quantize_ship_factor(self.Q, ship)
        return EigenBasis(Q=Q), bits


class DCTBasis(RotationBasis):
    """Fixed orthonormal DCT-II rotation: the same machinery as `EigenBasis`
    but data-independent — server and clients both generate it, so shipment
    is free.  A useful control in the basis×compressor grid: it shows how
    much of the eigenbasis win is *data adaptivity* vs mere decorrelation."""

    def __init__(self, d: int):
        j = np.arange(d)[:, None]      # frequency index
        t = np.arange(d)[None, :]      # position index
        C = np.sqrt(2.0 / d) * np.cos(np.pi * (t + 0.5) * j / d)
        C[0] *= np.sqrt(0.5)           # orthonormalize the DC row
        super().__init__(Q=jnp.asarray(C.T))  # columns = DCT basis vectors


# --------------------------------------------------------------------------
# compressed basis shipment: quantize the factors that actually travel
# --------------------------------------------------------------------------
def quantize_ship_factor(M: jax.Array, ship: comm.BasisShipSpec
                         ) -> Tuple[jax.Array, float]:
    """One shipped (rows, cols) basis factor after the wire: quantized
    values and the exact bits they cost.

    The quantization is what the receiver actually rotates with — not just
    an accounting fiction:

      * ``col_frac < 1`` zeroes everything but each column's top
        ``⌈col_frac·rows⌉`` magnitudes (selection via the shared
        `compressors.topk_keep_mask` backend, so REPRO_BL_PALLAS=1 swaps
        the search kernel without changing the kept set);
      * ``float_bits = 16`` is a bfloat16 round-trip; ``8`` is symmetric
        per-column int8 (scale = max|col|/127, one f32 scale per column);
        ``32``/``64`` are plain casts (identity for factors already that
        wide).

    Bits are priced by `comm.price` on `ship.wire` with
    `ship.factor_counts` — the same Counts→bits algebra every other leg
    uses.  Returns the factor in its original dtype (every quantized value
    is exactly representable there) and the bits as a python float, so
    shipment billing stays configuration-static."""
    M = jnp.asarray(M)
    if M.ndim != 2:
        raise ValueError(f"shipped basis factors are 2-D, got {M.shape}")
    rows, cols = int(M.shape[0]), int(M.shape[1])
    W = M if ship.float_bits == 64 else M.astype(jnp.float32)
    if not ship.dense:
        from . import compressors  # local import: compressors imports comm

        k = max(1, min(rows, int(np.ceil(ship.col_frac * rows))))
        keep = compressors.topk_keep_mask(W.T, k).T
        W = jnp.where(keep, W, jnp.zeros_like(W))
    if ship.float_bits == 16:
        W = W.astype(jnp.bfloat16).astype(jnp.float32)
    elif ship.float_bits == 8:
        scale = jnp.max(jnp.abs(W), axis=0, keepdims=True) / 127.0
        scale = jnp.where(scale > 0.0, scale, 1.0)
        W = jnp.clip(jnp.round(W / scale), -127.0, 127.0) * scale
    counts = ship.factor_counts(rows, cols)
    bits = float(comm.price(ship.wire, counts))
    return W.astype(M.dtype), bits


def _two_sided(A: jax.Array, g: jax.Array, B: jax.Array) -> jax.Array:
    """One rotated leaf: ``A @ g @ B`` (left-associated, matching python
    ``@``).  Client-stacked f32 leaves route through the fused Pallas
    transform kernel under ``REPRO_BL_PALLAS=1`` — bitwise the XLA batched
    matmul in interpret mode (kernels/basis_transform.py), so the flag
    never perturbs trajectories."""
    if (g.ndim == 3 and g.dtype == jnp.float32
            and A.dtype == jnp.float32 and B.dtype == jnp.float32
            and os.environ.get("REPRO_BL_PALLAS", "0") == "1"):
        from repro.kernels import ops

        return ops.basis_transform(A, g, B)
    return A @ g @ B


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PerLayerSVDBasis:
    """Pytree basis for DNN parameter trees (the BL-DNN layer, §2.3 carried
    beyond the paper): every 2-D weight leaf gets a COMPLETE orthogonal
    basis (U_ℓ, V_ℓ) from the SVD of its initialization — the weight matrix
    plays the data-matrix role — and its gradient is communicated as the
    rotated coefficients U_ℓᵀ g V_ℓ.  Non-matrix leaves (biases, norms)
    pass through unrotated.

    Unlike the d×d `MatrixBasis` classes this operates on whole parameter
    *pytrees*: `rotate`/`unrotate` are leaf-aligned maps, and leaves may
    carry a leading client axis (the round engine's (n, ...) stacks) — the
    rotations broadcast over it.  The basis is fleet-global (every client
    derives it from the shared initialization), so the engine replicates it
    across the client mesh instead of sharding it (`MethodSpec.
    basis_replicated`).

    Completeness matters: `full_matrices=True` in the construction — a
    truncated V would silently project out every gradient component outside
    the weight's row space.

    A leaf of more dimensions (a layer stack's leading axis, an attention
    weight's ``(d, heads, head_dim)``) is rotated slice by slice on a
    *matrix view* (``views``): its first ``n_stack`` axes index slices, the
    next ``n_rows`` axes are the rows and the rest the columns — ``wq``
    ``(G, d, heads, hd)`` is G matrices ``(d, heads·hd)``, ``wo``
    ``(G, heads, hd, d)`` is G matrices ``(heads·hd, d)`` — and U, V stack
    one factor per slice.
    """

    #: per-leaf entries ordered like ``jax.tree.leaves(params)``:
    #: ``(U, V)`` for rotated leaves, ``None`` for pass-through leaves.
    UV: tuple
    #: static, per leaf: None (a 2-D leaf, or one passed through) or the
    #: matrix view ``(leaf shape, n_stack, n_rows)`` of a stacked leaf
    views: Optional[tuple] = None

    def tree_flatten(self):
        return (self.UV,), self.views

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(UV=children[0], views=aux)

    @property
    def slices_rotated(self) -> int:
        """Matrices rotated: one per 2-D leaf, one per slice of a viewed
        leaf."""
        views = self.views or (None,) * len(self.UV)
        return sum(1 if view is None else int(np.prod(view[0][:view[1]]))
                   for uv, view in zip(self.UV, views) if uv is not None)

    def _map(self, fn, tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if len(leaves) != len(self.UV):
            raise ValueError(
                f"tree has {len(leaves)} leaves but basis covers "
                f"{len(self.UV)} — built from a different parameter tree?")
        views = self.views or (None,) * len(leaves)
        out = []
        for uv, view, leaf in zip(self.UV, views, leaves):
            if uv is None:
                out.append(leaf)
            elif view is None:
                out.append(fn(uv[0], uv[1], leaf))
            else:
                lead = leaf.shape[:leaf.ndim - len(view[0])]
                mats = leaf.reshape(lead + _matrix_view(*view))
                out.append(fn(uv[0], uv[1], mats).reshape(leaf.shape))
        return treedef.unflatten(out)

    def rotate(self, tree):
        """Leaf-wise forward transform U_ℓᵀ g V_ℓ (complete basis ⇒ the
        coefficient tensor keeps the leaf's own shape).  Leaves may carry
        leading batch/client axes — matrix products broadcast over them."""
        return self._map(
            lambda U, V, g: _two_sided(jnp.swapaxes(U, -1, -2),
                                       g.astype(U.dtype), V),
            tree)

    def unrotate(self, tree):
        """Exact inverse of `rotate`: U_ℓ c V_ℓᵀ per rotated leaf."""
        return self._map(
            lambda U, V, c: _two_sided(U, c, jnp.swapaxes(V, -1, -2)), tree)

    def ship_floats(self) -> float:
        """One-time basis shipment size in floats (Σ_ℓ |U_ℓ| + |V_ℓ| — the
        Table-1 analogue; bill it on the ledger's ``basis_ship`` leg at the
        shipping wire's float width)."""
        return float(sum(uv[0].size + uv[1].size
                         for uv in self.UV if uv is not None))

    def shipped(self, ship: comm.BasisShipSpec
                ) -> Tuple["PerLayerSVDBasis", float]:
        """The basis as it arrives after a compressed shipment: every
        rotated leaf's (U_ℓ, V_ℓ) quantized per `ship`
        (`quantize_ship_factor`, slice by slice for a stacked leaf) and the
        summed exact bits of the shipment.  The default spec (f32, dense)
        is the identity on these f32 factors and prices exactly
        ``ship_floats() × 32`` — legacy billing."""
        def factor(M):
            if M.ndim == 2:
                return quantize_ship_factor(M, ship)
            parts = [quantize_ship_factor(m, ship) for m in M]
            return (jnp.stack([q for q, _ in parts]),
                    sum(b for _, b in parts))

        new_uv, bits = [], 0.0
        for uv in self.UV:
            if uv is None:
                new_uv.append(None)
                continue
            U, bu = factor(uv[0])
            V, bv = factor(uv[1])
            new_uv.append((U, V))
            bits += bu + bv
        return type(self)(UV=tuple(new_uv), views=self.views), bits


def _matrix_view(shape, n_stack: int, n_rows: int) -> tuple:
    """(slices, rows, cols) of a leaf's matrix view."""
    shape = tuple(int(s) for s in shape)
    return (int(np.prod(shape[:n_stack])),
            int(np.prod(shape[n_stack:n_stack + n_rows])),
            int(np.prod(shape[n_stack + n_rows:])))


def per_layer_svd_basis(params, use_basis: bool = True, min_dim: int = 2,
                        views=None) -> PerLayerSVDBasis:
    """Build the `PerLayerSVDBasis` of a parameter pytree's initialization.

    Every 2-D leaf with both dims ≥ `min_dim` gets (U, V) from its full
    SVD; 1-D leaves pass through.  ``views`` (aligned with
    ``jax.tree.leaves(params)``) gives a leaf of more dimensions its matrix
    view ``(n_stack, n_rows)`` (see `PerLayerSVDBasis`), and each slice of
    it gets (U, V) from its own full SVD, taken by NumPy on the host; a leaf of 3 or more dimensions
    without one is an error, since it cannot be rotated and would silently
    ride in the standard basis.  ``use_basis=False`` returns the identity
    basis (no rotations, zero shipment) — the no-basis control in the
    basis-vs-compressor experiments.
    """
    leaves = jax.tree_util.tree_leaves(params)
    views = tuple(views) if views is not None else (None,) * len(leaves)
    if len(views) != len(leaves):
        raise ValueError(f"{len(views)} views for {len(leaves)} leaves")
    out, kept = [], []
    for p, view in zip(leaves, views):
        if not use_basis:
            out.append(None)
            kept.append(None)
            continue
        if view is not None:
            # on the host: the TPU compiler fails on a 4096² SVD
            shape = tuple(int(s) for s in p.shape)
            mats = np.asarray(p, np.float32).reshape(_matrix_view(shape, *view))
            u, _, vt = np.linalg.svd(mats, full_matrices=True)
            out.append((jnp.asarray(u), jnp.asarray(np.swapaxes(vt, -1, -2))))
            kept.append((shape,) + tuple(view))
            continue
        if p.ndim >= 3:
            raise ValueError(
                f"per_layer_svd cannot rotate a leaf of shape {p.shape} "
                "without a matrix view (views=)")
        if p.ndim == 2 and min(p.shape) >= min_dim:
            u, _, vt = jnp.linalg.svd(p.astype(jnp.float32),
                                      full_matrices=True)
            out.append((u, vt.T))
        else:
            out.append(None)
        kept.append(None)
    has_view = any(v is not None for v in kept)
    return PerLayerSVDBasis(UV=tuple(out),
                            views=tuple(kept) if has_view else None)


@jax.tree_util.register_pytree_node_class
class StructuredTreeBasis(PerLayerSVDBasis):
    """Pytree basis whose per-leaf rotations are CONVENTIONS (DCT-II or
    Walsh–Hadamard), generalizing the d×d `DCTBasis` to parameter trees:
    the same `PerLayerSVDBasis` rotation machinery (and the same Pallas
    transform kernel under ``REPRO_BL_PALLAS=1``), but both sides generate
    the factors from the leaf shapes alone — nothing data-dependent ever
    travels, so ``ship_floats() == 0`` and `shipped` is the identity at
    zero bits.  The decorrelation-vs-adaptivity control of the BL-DNN
    grid: how much of the per-layer-SVD win survives when the basis is
    free?"""

    def ship_floats(self) -> float:
        return 0.0

    def shipped(self, ship: comm.BasisShipSpec
                ) -> Tuple["StructuredTreeBasis", float]:
        """Conventions don't travel: the factors are never on the wire, so
        quantizing them would model a cost (and a fidelity loss) that
        doesn't exist.  Identity, zero bits."""
        return self, 0.0


def _dct_matrix(d: int) -> jax.Array:
    """Orthonormal DCT-II factor (columns = basis vectors), f32 — the same
    construction as `DCTBasis` at any dimension."""
    j = np.arange(d)[:, None]
    t = np.arange(d)[None, :]
    C = np.sqrt(2.0 / d) * np.cos(np.pi * (t + 0.5) * j / d)
    C[0] *= np.sqrt(0.5)
    return jnp.asarray(C.T, jnp.float32)


def _hadamard_matrix(d: int) -> jax.Array:
    """Normalized Walsh–Hadamard factor H_d/√d for power-of-two d; identity
    otherwise (Sylvester's construction only exists at powers of two — a
    non-pow2 leaf axis simply passes through unrotated on that side)."""
    if d & (d - 1):
        return jnp.eye(d, dtype=jnp.float32)
    H = np.array([[1.0]])
    while H.shape[0] < d:
        H = np.block([[H, H], [H, -H]])
    return jnp.asarray(H / np.sqrt(d), jnp.float32)


def structured_tree_basis(params, kind: str = "dct",
                          min_dim: int = 2) -> StructuredTreeBasis:
    """Build the free structured basis of a parameter pytree: every 2-D
    leaf with both dims ≥ `min_dim` gets fixed orthogonal (U, V) factors
    from its SHAPE alone (``kind`` ∈ {"dct", "hadamard"}); other leaves
    pass through.  Zero shipment by construction."""
    factories = {"dct": _dct_matrix, "hadamard": _hadamard_matrix}
    if kind not in factories:
        raise KeyError(f"unknown structured-basis kind {kind!r}; "
                       f"one of {sorted(factories)}")
    make = factories[kind]
    out = []
    for p in jax.tree_util.tree_leaves(params):
        if p.ndim == 2 and min(p.shape) >= min_dim:
            out.append((make(int(p.shape[0])), make(int(p.shape[1]))))
        else:
            out.append(None)
    return StructuredTreeBasis(UV=tuple(out))


def orth_basis_from_data(A_data: jax.Array, rcond: float = 1e-10) -> DataOuterBasis:
    """Orthonormal basis of the row space of the client's data matrix (m, d).

    Mirrors the paper's use of scipy.linalg.orth on the feature matrix (§6.1).
    """
    # SVD of (m, d): row space spanned by right singular vectors
    _, s, vt = jnp.linalg.svd(A_data, full_matrices=False)
    tol = s.max() * max(A_data.shape) * rcond
    r = int(jnp.sum(s > tol))
    r = max(r, 1)
    V = vt[:r].T  # (d, r)
    return DataOuterBasis(V=V)


def eigen_basis_from_clients(clients, x0: Optional[jax.Array] = None) -> List[EigenBasis]:
    """One shared `EigenBasis` per client: eigenvectors of the fleet's
    averaged initial Hessian ∇²f(x⁰) (x⁰ = 0 by default, as the experiments
    initialize).  Returns the SAME basis object for every client — the
    batched engine exploits that (one (d, d) Q, not n copies)."""
    from . import glm  # local import: glm is a sibling leaf module

    clients = list(clients)
    d = int(clients[0].A.shape[1])
    if x0 is None:
        x0 = jnp.zeros(d, clients[0].A.dtype)
    H0 = glm.global_hess(clients, x0)
    _, Q = jnp.linalg.eigh((H0 + H0.T) / 2.0)
    basis = EigenBasis(Q=Q)
    return [basis for _ in clients]


def basis_transmission_bits(basis: MatrixBasis, float_bits: int = FLOAT_BITS) -> float:
    """One-time cost of shipping the basis to the server (Table 1: rd floats
    for the data basis, d² for an eigenbasis).

    Standard/symmetric/PSD/DCT bases are conventions — zero marginal cost.
    """
    if isinstance(basis, DataOuterBasis):
        return float(basis.d * basis.r * float_bits)
    if isinstance(basis, EigenBasis):
        return float(basis.d * basis.d * float_bits)
    return 0.0


# --------------------------------------------------------------------------
# registry: "which basis" as a first-class configuration axis
# --------------------------------------------------------------------------
BasisFactory = Callable[..., List[MatrixBasis]]
BASIS_REGISTRY: Dict[str, BasisFactory] = {}
#: names whose basis operates on parameter *pytrees* (e.g. ``per_layer_svd``)
#: rather than d×d matrices — they take the parameter tree where matrix
#: bases take the client fleet, and the d×d contract tests / benchmark
#: grids skip them (see `is_pytree_basis`).
PYTREE_BASES: set = set()


def register_basis(name: str, *, pytree: bool = False):
    """Register a fleet-level basis factory ``factory(clients, x0=None,
    **kw) -> List[MatrixBasis]`` under `name`.

    ``pytree=True`` marks a pytree-basis factory ``factory(params, x0=None,
    **kw)`` (first argument is a parameter pytree, not a client list)."""
    def deco(factory: BasisFactory) -> BasisFactory:
        BASIS_REGISTRY[name] = factory
        if pytree:
            PYTREE_BASES.add(name)
        return factory
    return deco


def available_bases() -> List[str]:
    return sorted(BASIS_REGISTRY)


def is_pytree_basis(name: str) -> bool:
    """True for registered bases that transform parameter pytrees (DNN
    workloads) instead of d×d coefficient matrices."""
    return name in PYTREE_BASES


def make_bases(name: str, clients: Sequence, x0: Optional[jax.Array] = None,
               **kw) -> List[MatrixBasis]:
    """Build the per-client basis list for a registered basis name.

    Args:
      name: registry key (see `available_bases()`).
      clients: the client fleet (`glm.ClientData` sequence) — data-adaptive
        bases derive their parameters from it.  For pytree bases
        (`is_pytree_basis`) this is the parameter pytree instead (the
        shared initialization every client derives the basis from).
      x0: initial iterate for bases anchored there (`eigen`); ignored by
        data-independent bases.
      **kw: factory-specific options (e.g. ``rcond`` for `data_outer`).

    Returns:
      One `MatrixBasis` per client (shared-object for global bases —
      the batched engine exploits the identity).  Pytree-basis factories
      return the fleet-global basis object itself (e.g.
      `PerLayerSVDBasis`), not a per-client list.
    """
    if name not in BASIS_REGISTRY:
        raise KeyError(
            f"unknown basis {name!r}; registered: {available_bases()}")
    if name in PYTREE_BASES:
        return BASIS_REGISTRY[name](clients, x0=x0, **kw)
    return BASIS_REGISTRY[name](list(clients), x0=x0, **kw)


def _fleet_d(clients) -> int:
    return int(clients[0].A.shape[1])


@register_basis("standard")
def _standard_bases(clients, x0=None):
    d = _fleet_d(clients)
    return [StandardBasis(d) for _ in clients]


@register_basis("symmetric")
def _symmetric_bases(clients, x0=None):
    d = _fleet_d(clients)
    return [SymmetricBasis(d) for _ in clients]


@register_basis("psd")
def _psd_bases(clients, x0=None):
    d = _fleet_d(clients)
    return [PSDBasis(d) for _ in clients]


@register_basis("data_outer")
def _data_outer_bases(clients, x0=None, rcond: float = 1e-10):
    return [orth_basis_from_data(c.A, rcond=rcond) for c in clients]


@register_basis("eigen")
def _eigen_bases(clients, x0=None):
    return eigen_basis_from_clients(clients, x0=x0)


@register_basis("dct")
def _dct_bases(clients, x0=None):
    basis = DCTBasis(_fleet_d(clients))
    return [basis for _ in clients]


@register_basis("per_layer_svd", pytree=True)
def _per_layer_svd_bases(params, x0=None, use_basis: bool = True,
                         views=None):
    """Pytree basis of a DNN parameter tree (the BL-DNN workload): one
    complete per-layer SVD rotation per weight matrix (or per slice of a
    stacked leaf's matrix view), shared by the whole fleet.  Shipment
    (Σ_ℓ |U_ℓ|+|V_ℓ| floats) bills on ``basis_ship``."""
    return per_layer_svd_basis(params, use_basis=use_basis, views=views)


@register_basis("dct_tree", pytree=True)
def _dct_tree_bases(params, x0=None, min_dim: int = 2):
    """Free structured pytree basis: per-leaf DCT-II rotations generated
    from leaf shapes by both sides — zero ``basis_ship`` bits."""
    return structured_tree_basis(params, kind="dct", min_dim=min_dim)


@register_basis("hadamard_tree", pytree=True)
def _hadamard_tree_bases(params, x0=None, min_dim: int = 2):
    """Free structured pytree basis: per-leaf normalized Walsh–Hadamard
    rotations (power-of-two axes; identity otherwise) — zero
    ``basis_ship`` bits."""
    return structured_tree_basis(params, kind="hadamard", min_dim=min_dim)
