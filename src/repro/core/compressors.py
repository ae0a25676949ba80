"""Matrix/vector compression operators (paper §3, §A.2, §A.3).

One natively-batched contract: ``compress(keys, x)`` takes a stack of n
inputs (leading client axis) plus per-client PRNG keys ``(n, 2)`` and
returns ``(compressed_dense, counts)`` — the compressed tensors (zeros are
what got dropped) and a `repro.core.comm.Counts` record of what actually
hit the wire.  Compressors never compute bits: each declares a
`WireFormat` (`.wire`) and the comm layer prices counts
(``comm.price(comp.wire, counts)``).  Two contract classes:

  * contraction (Eq. 6):  E‖A − C(A)‖_F² ≤ (1−δ)‖A‖_F²
  * unbiased   (Eq. 7):  E[C(A)] = A,  E‖C(A)‖_F² ≤ (ω+1)‖A‖_F²

``keys=None`` is accepted only by deterministic compressors — stochastic
ones raise instead of silently substituting a fixed key (which would make
every "random" draw identical).

The single-client convenience ``comp(key, x)`` is a thin adapter over the
same batched implementation (n = 1) that additionally prices the message —
it exists for the op-by-op reference backend and tests; there is exactly
one selection/quantization implementation per operator.

|·|-Top-K selection (the batched engine's hot spot) is one shared routine,
`topk_keep_mask`, consumed by both `TopK` and `ComposedTopK`.  Its
threshold search runs on an f32 copy (XLA's CPU sort/top_k on f64 is ~75×
slower) through one of two parity-pinned backends:

  * default: barrier'd ``lax.top_k`` (the barriers stop XLA rewriting a
    partially-dead top_k into a full stable sort);
  * ``REPRO_BL_PALLAS=1``: the exact bitwise-binary-search Pallas kernel
    (`repro.kernels.topk_threshold`) — same threshold bitwise, so the
    shared tie-break mask selects identical entries and trajectories are
    unchanged (tests/test_pallas_parity.py).
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import comm
from .comm import FLOAT_BITS, INDEX_BITS  # noqa: F401  (historical re-export)


def _numel(x: jax.Array) -> int:
    """Per-client element count of a client-stacked (n, ...) array."""
    n = 1
    for s in x.shape[1:]:
        n *= s
    return n


def _full(n: int, value) -> jax.Array:
    return jnp.full((n,), value, jnp.float64)


class Compressor:
    """Base class. Subclasses set `is_unbiased`, `delta` or `omega`."""

    is_unbiased: bool = False
    #: contraction parameter δ ∈ (0,1]  (contractive compressors)
    delta: Optional[float] = None
    #: variance parameter ω ≥ 0        (unbiased compressors)
    omega: Optional[float] = None
    #: True if C(A) is deterministic given A (Asm. 4.4(ii)/4.6(ii))
    deterministic: bool = False

    @property
    def stochastic(self) -> bool:
        return not self.deterministic

    @property
    def wire(self):
        """`comm.WireFormat` (or tuple tree, for composed codecs) pricing
        this operator's `Counts`."""
        return comm.WireFormat()

    def compress(self, keys: Optional[jax.Array], x: jax.Array) -> Tuple[jax.Array, comm.Counts]:
        """Compress a client-stacked batch (the one batched contract).

        Args:
          keys: per-client PRNG keys, shape (n, 2); None is accepted only
            by deterministic compressors (stochastic ones raise).
          x: (n, ...) stack of per-client tensors (matrices for the
            Hessian codecs, vectors for model/gradient streams).

        Returns:
          (compressed, counts): ``compressed`` is (n, ...) dense with
          zeros where entries were dropped (Eq. 6 contraction / Eq. 7
          unbiased contract applies per client); ``counts`` is a
          `comm.Counts` whose leaves are per-client (n,) message counts —
          price them with ``comm.price(self.wire, counts)``.
        """
        raise NotImplementedError

    def compress_sum(self, keys: Optional[jax.Array], x: jax.Array
                     ) -> Tuple[jax.Array, comm.Counts, jax.Array]:
        """Fused compress-then-reduce: `compress` plus the LOCAL sum of the
        compressed stack over the client axis.

        Returns ``(compressed, counts, local_sum)`` with ``local_sum ==
        compressed.sum(axis=0)`` (payload-shaped).  The default is the
        obvious two-pass composition; codecs with a fused kernel override
        it (Top-K under ``REPRO_BL_PALLAS=1`` computes the selection
        threshold and the partial sum in one pass — see
        `repro.kernels.topk_threshold.topk_compress_sum`).  Consumers feed
        the sum to `rounds.Reducer.tree_mean_presummed`, which lets the
        bandwidth-optimal sharded path reduce the pre-summed payload
        instead of gathering the dense stack."""
        dense, counts = self.compress(keys, x)
        return dense, counts, jnp.sum(dense, axis=0)

    def _require_keys(self, keys: Optional[jax.Array], n: int) -> Optional[jax.Array]:
        if keys is None:
            if self.stochastic:
                raise ValueError(
                    f"{type(self).__name__} is stochastic: compress() needs "
                    "per-client PRNG keys (n, 2), got None — a substituted "
                    "fixed key would repeat the same draw every call")
            return None
        return keys

    def __call__(self, key: Optional[jax.Array], x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Single-client adapter: compress one tensor and price it.
        Returns (compressed_dense, bits_transmitted)."""
        keys = None if key is None else jnp.asarray(key)[None]
        dense, counts = self.compress(keys, x[None])
        return dense[0], comm.price(self.wire, counts)[0]

    def alpha(self) -> float:
        """Recommended Hessian-learning step size: 1/(ω+1) for unbiased
        compressors (Eq. 7), 1 for contractive ones (Eq. 6)."""
        if self.is_unbiased:
            return 1.0 / (self.omega + 1.0)
        return 1.0


@dataclasses.dataclass(unsafe_hash=True)
class Identity(Compressor):
    """No compression; full tensor on the wire."""
    is_unbiased = True
    omega = 0.0
    delta = 1.0
    deterministic = True

    def compress(self, keys, x):
        return x, comm.Counts(floats=_full(x.shape[0], _numel(x)))


# --------------------------------------------------------------------------
# shared |·|-Top-K selection (one implementation, two backends)
# --------------------------------------------------------------------------
def _selection_threshold(a32: jax.Array, k: int) -> jax.Array:
    """k-th largest per row of non-negative f32 `a32` (..., T) → (..., 1).

    Backends return bitwise-identical thresholds; see module docstring."""
    if os.environ.get("REPRO_BL_PALLAS", "0") == "1":
        from repro.kernels.topk_threshold import topk_row_threshold

        t = topk_row_threshold(a32.reshape((-1,) + a32.shape[-1:]), k)
        return t.reshape(a32.shape[:-1] + (1,))
    vals, idx = jax.lax.top_k(a32, k)
    # keep both outputs alive: with the indices dead, XLA rewrites top_k into
    # a full stable sort (~12× slower on CPU for the d² coefficient arrays).
    # Barrier each output separately — a barrier consuming the top_k tuple
    # itself crashes XLA's TopkDecomposer under multi-device shard_map
    # (CreateVariadicComparator expects get-tuple-element users).
    vals = jax.lax.optimization_barrier(vals)
    _ = jax.lax.optimization_barrier(idx)
    return vals[..., -1:]


def topk_keep_mask(v: jax.Array, k: int) -> jax.Array:
    """Boolean mask of the K largest-|v| entries along the last axis.

    Exactly K entries are kept per row: entries strictly above the f32
    threshold, then earliest-index entries inside the threshold tie group
    (sub-f32-ulp value differences inside the group are broken by index).
    Scatter-free on purpose: mask + `where` instead of `.at[idx].set`.

    Public building block for Top-K-style selection outside the compressor
    classes (exactly-k semantics, tie handling and the Pallas/XLA backend
    switch in one place).
    """
    from repro.kernels.topk_threshold import keep_mask

    a32 = jnp.abs(v).astype(jnp.float32)
    return keep_mask(a32, _selection_threshold(a32, k), k)


#: historical private name — new code should import `topk_keep_mask`.
_topk_keep_mask = topk_keep_mask


@dataclasses.dataclass(unsafe_hash=True)
class TopK(Compressor):
    """Greedy sparsification (Eq. 21): keep K largest-|.| entries.

    Contractive with δ = K/numel.  Deterministic.
    """
    k: int
    symmetrize: bool = False  # apply to upper-triangular half, mirror (paper §A.2)

    def __post_init__(self):
        self.deterministic = True

    def compress(self, keys, x):
        n = x.shape[0]
        if self.symmetrize and x.ndim == 3 and x.shape[1] == x.shape[2]:
            d = x.shape[1]
            iu = jnp.triu_indices(d)
            v = x[:, iu[0], iu[1]]                      # (n, T)
            kk = min(self.k, v.shape[1])
            keep_tri = topk_keep_mask(v, kk)
            # gather the triangular mask back to the dense upper half
            # (static index map — no scatter)
            pos = jnp.zeros((d, d), jnp.int32).at[iu].set(
                jnp.arange(v.shape[1], dtype=jnp.int32))
            upper = jnp.triu(jnp.ones((d, d), bool))
            keep_full = keep_tri[:, pos] & upper
            out = jnp.where(keep_full, x, 0.0)
            out = out + jnp.transpose(jnp.triu(out, 1), (0, 2, 1))
            c = _full(n, kk)
            return out, comm.Counts(floats=c, indices=c)
        v = x.reshape(n, -1)
        kk = min(self.k, v.shape[1])
        out = jnp.where(topk_keep_mask(v, kk), v, 0.0).reshape(x.shape)
        c = _full(n, kk)
        return out, comm.Counts(floats=c, indices=c)

    def compress_sum(self, keys, x):
        # fused selection + local client-axis partial sum in one Pallas
        # pass; the kernel's threshold/tie-break path is the bitwise-pinned
        # one, so dense/counts/sum all match the two-pass default exactly
        # (tests/test_pallas_parity.py).  f32 flat payloads only — the
        # symmetrized matrix codec and f64 GLM streams take the default.
        if (self.symmetrize or x.dtype != jnp.float32
                or os.environ.get("REPRO_BL_PALLAS", "0") != "1"):
            return super().compress_sum(keys, x)
        from repro.kernels.topk_threshold import topk_compress_sum

        n = x.shape[0]
        v = x.reshape(n, -1)
        kk = min(self.k, v.shape[1])
        out, s = topk_compress_sum(v, kk)
        c = _full(n, kk)
        return (out.reshape(x.shape), comm.Counts(floats=c, indices=c),
                s.reshape(x.shape[1:]))

    @property
    def _delta_for(self):
        return None  # depends on input size; use delta_for(numel)

    def delta_for(self, numel: int) -> float:
        return min(self.k, numel) / numel


@dataclasses.dataclass(unsafe_hash=True)
class RandK(Compressor):
    """Random sparsification (Eq. 22): unbiased, ω = numel/K − 1."""
    k: int

    def __post_init__(self):
        self.is_unbiased = True

    def compress(self, keys, x):
        n = x.shape[0]
        keys = self._require_keys(keys, n)
        numel = _numel(x)
        kk = min(self.k, numel)
        scale = numel / kk

        def one(key, xi):
            v = xi.reshape(-1)
            idx = jax.random.choice(key, numel, shape=(kk,), replace=False)
            return jnp.zeros_like(v).at[idx].set(v[idx] * scale).reshape(xi.shape)

        c = _full(n, kk)
        return jax.vmap(one)(keys, x), comm.Counts(floats=c, indices=c)

    def omega_for(self, numel: int) -> float:
        return numel / min(self.k, numel) - 1.0

    def alpha_for(self, numel: int) -> float:
        return 1.0 / (self.omega_for(numel) + 1.0)


@dataclasses.dataclass(unsafe_hash=True)
class RankR(Compressor):
    """Low-rank approximation via SVD (Eq. 19–20).

    Contractive with δ = R/d on d×d matrices [Safaryan et al., 2021].
    Symmetric input ⇒ symmetric output automatically.
    """
    r: int

    def __post_init__(self):
        self.deterministic = True

    def compress(self, keys, x):
        assert x.ndim == 3, "Rank-R needs a stack of matrices"
        n = x.shape[0]
        u, s, vt = jnp.linalg.svd(x, full_matrices=False)
        rr = min(self.r, s.shape[-1])
        out = jnp.matmul(u[:, :, :rr] * s[:, None, :rr], vt[:, :rr, :])
        # wire format: R singular triples (u_i, σ_i, v_i)
        c = _full(n, rr * (x.shape[1] + x.shape[2] + 1))
        return out, comm.Counts(floats=c)

    def delta_for(self, d: int) -> float:
        return min(self.r, d) / d


def _dither_vals(key, x, s, q=2):
    """Random dithering values (Eq. 17–18) with s levels, q-norm."""
    v = x.reshape(-1)
    raw_norm = jnp.linalg.norm(v, ord=q)
    norm = jnp.where(raw_norm == 0, 1.0, raw_norm)
    a = jnp.abs(v) / norm * s          # in [0, s]
    low = jnp.floor(a)
    pup = a - low                       # P[round up]
    up = jax.random.bernoulli(key, pup.astype(jnp.float32))
    lev = low + up
    out = jnp.sign(v) * norm * lev / s
    out = jnp.where(raw_norm == 0, 0.0, out)
    return out.reshape(x.shape)


def _dither_level_bits(s: int) -> int:
    return math.ceil(math.log2(s + 1))


@dataclasses.dataclass(unsafe_hash=True)
class RandomDithering(Compressor):
    """Unbiased; ω ≤ min(d/s², √d/s) for q=2 [Alistarh et al. 2017].

    Wire: 1 norm float + per-entry (sign + ⌈log₂(s+1)⌉ level) bits."""
    s: int
    q: int = 2

    def __post_init__(self):
        self.is_unbiased = True

    @property
    def wire(self):
        return comm.WireFormat(entry_bits=1 + _dither_level_bits(self.s))

    def compress(self, keys, x):
        n = x.shape[0]
        keys = self._require_keys(keys, n)
        out = jax.vmap(lambda k, xi: _dither_vals(k, xi, self.s, self.q))(keys, x)
        return out, comm.Counts(floats=_full(n, 1), entries=_full(n, _numel(x)))

    def omega_for(self, numel: int) -> float:
        return min(numel / self.s**2, numel**0.5 / self.s)


@dataclasses.dataclass(unsafe_hash=True)
class NaturalCompression(Compressor):
    """Round |x| to a power of two, randomly up/down (unbiased, ω = 1/8).

    Wire format: sign + 8-bit exponent = 9 bits/entry.
    """
    def __post_init__(self):
        self.is_unbiased = True
        self.omega = 1.0 / 8.0

    @property
    def wire(self):
        return comm.WireFormat(entry_bits=9)

    def compress(self, keys, x):
        n = x.shape[0]
        keys = self._require_keys(keys, n)

        def one(key, xi):
            v = xi.reshape(-1)
            nz = v != 0
            absv = jnp.where(nz, jnp.abs(v), 1.0)
            e = jnp.floor(jnp.log2(absv))
            low = jnp.exp2(e)
            pup = (absv - low) / low        # ∈ [0,1): P[round to 2^{e+1}]
            up = jax.random.bernoulli(key, pup.astype(jnp.float32))
            out = jnp.sign(v) * low * jnp.where(up, 2.0, 1.0)
            return jnp.where(nz, out, 0.0).reshape(xi.shape)

        out = jax.vmap(one)(keys, x)
        return out, comm.Counts(entries=_full(n, _numel(x)))


@dataclasses.dataclass(unsafe_hash=True)
class ComposedTopK(Compressor):
    """Top-K followed by an unbiased compressor on the kept values (§A.5).

    RTop-K: inner = RandomDithering(s=√K);  NTop-K: inner = NaturalCompression.
    Contractive (composition of a contraction with an unbiased op, scaled by
    1/(ω+1), remains a contraction — Qian et al. 2021).

    Selection is the shared `topk_keep_mask`; the kept values are compacted
    to (n, K) slots by a cumsum scatter (index order), run through the inner
    compressor's own batched contract, and gathered back — no second Top-K
    implementation.
    """
    k: int
    inner: Compressor
    unbias_correct: bool = True

    def __post_init__(self):
        self.deterministic = self.inner.deterministic

    @property
    def wire(self):
        return (comm.WireFormat(), self.inner.wire)

    def compress(self, keys, x):
        n = x.shape[0]
        v = x.reshape(n, -1)
        kk = min(self.k, v.shape[1])
        keys = self._require_keys(keys, n)
        mask = topk_keep_mask(v, kk)
        slot = jnp.cumsum(mask, axis=-1) - 1            # target slot per kept
        slot = jnp.where(mask, slot, kk)                # park dropped at k
        rows = jnp.arange(n)[:, None]
        kept = jnp.zeros((n, kk + 1), v.dtype).at[rows, slot].add(
            jnp.where(mask, v, 0.0))[:, :kk]
        cv, inner_counts = self.inner.compress(keys, kept)
        if self.unbias_correct:
            om = getattr(self.inner, "omega", None)
            if om is None:
                om = self.inner.omega_for(kk)
            cv = cv / (om + 1.0)
        cvp = jnp.concatenate([cv, jnp.zeros((n, 1), cv.dtype)], axis=1)
        out = jnp.where(mask, jnp.take_along_axis(cvp, slot, axis=1), 0.0)
        counts = (comm.Counts(indices=_full(n, kk)), inner_counts)
        return out.reshape(x.shape), counts


@dataclasses.dataclass(unsafe_hash=True)
class ComposedRankR(Compressor):
    """C1 of §3: Rank-R with unbiasedly-compressed singular vectors.

    δ = R / (d (ω₁+1)(ω₂+1))  (Prop. 3.2).  We use a_i = b_i = 1.
    symmetrize=True gives C2 (Lemma 3.1 (ii)).
    """
    r: int
    inner_u: Compressor
    inner_v: Compressor
    symmetrize: bool = True

    def __post_init__(self):
        self.deterministic = (self.inner_u.deterministic
                              and self.inner_v.deterministic)

    @property
    def wire(self):
        return (comm.WireFormat(), self.inner_u.wire, self.inner_v.wire)

    def compress(self, keys, x):
        assert x.ndim == 3
        n = x.shape[0]
        keys = self._require_keys(keys, n)
        if keys is None:  # fully deterministic inners (degenerate but legal)
            keys = jnp.zeros((n, 2), jnp.uint32)
        u, s, vt = jnp.linalg.svd(x, full_matrices=False)
        rr = min(self.r, s.shape[-1])
        om1 = (self.inner_u.omega if self.inner_u.omega is not None
               else self.inner_u.omega_for(x.shape[1]))
        om2 = (self.inner_v.omega if self.inner_v.omega is not None
               else self.inner_v.omega_for(x.shape[2]))

        def one(key, ui, si, vti, xi):
            # keys laid out exactly as the historical op-by-op loop:
            # even → u-vector, odd → v-vector
            ks = jax.random.split(key, 2 * rr)
            qu, cu = self.inner_u.compress(ks[0::2], ui[:, :rr].T)   # (rr, m)
            qv, cvn = self.inner_v.compress(ks[1::2], vti[:rr, :])   # (rr, p)
            out = jnp.einsum("r,rm,rn->mn", si[:rr], qu, qv) / ((om1 + 1.0) * (om2 + 1.0))
            if self.symmetrize:
                out = jnp.where(jnp.allclose(xi, xi.T), (out + out.T) / 2.0, out)
            # fold the rr per-triple counts into one per-client record
            total = jax.tree.map(lambda a: jnp.sum(jnp.asarray(a, jnp.float64)),
                                 (cu, cvn))
            return out, total

        out, (cu, cvn) = jax.vmap(one)(keys, u, s, vt, x)
        counts = (comm.Counts(floats=_full(n, rr)), cu, cvn)
        return out, counts


@dataclasses.dataclass(unsafe_hash=True)
class BernoulliLazy(Compressor):
    """Lazy Bernoulli compressor (§A.8): send full tensor w.p. p, else zero.

    Unbiased with ω = 1/p − 1.
    """
    p: float

    def __post_init__(self):
        self.is_unbiased = True
        self.omega = 1.0 / self.p - 1.0

    def compress(self, keys, x):
        n = x.shape[0]
        keys = self._require_keys(keys, n)
        send = jax.vmap(lambda k: jax.random.bernoulli(k, self.p))(keys)
        bshape = (n,) + (1,) * (x.ndim - 1)
        out = jnp.where(send.reshape(bshape), x / self.p, jnp.zeros_like(x))
        floats = jnp.where(send, _numel(x), 0).astype(jnp.float64)
        return out, comm.Counts(floats=floats)


def rtopk(k: int) -> ComposedTopK:
    s = max(1, int(round(k ** 0.5)))
    return ComposedTopK(k=k, inner=RandomDithering(s=s))


def ntopk(k: int) -> ComposedTopK:
    return ComposedTopK(k=k, inner=NaturalCompression())


def rrankr(r: int, d: int) -> ComposedRankR:
    s = max(1, int(round(d ** 0.5)))
    return ComposedRankR(r=r, inner_u=RandomDithering(s=s), inner_v=RandomDithering(s=s))


def nrankr(r: int) -> ComposedRankR:
    return ComposedRankR(r=r, inner_u=NaturalCompression(), inner_v=NaturalCompression())
