"""Unified round engine: combinators + pluggable client-sharded aggregation.

Every method in this repo — BL1/BL2/BL3 (Algorithms 1–3), the FedNL family
they extend, and the first/second-order baselines — shares one round
skeleton: local Hessian/gradient compute → compressed-difference uplink →
server aggregate → (compressed) downlink.  This module factors that skeleton
into three pieces:

  1. **Combinators** — the shared round steps as small pure functions over
     client-stacked arrays: the compressed-shift recursion L ← L + αC(·−L)
     (`shift_update`; `tree_shift_update` maps it over parameter *pytrees*
     for the BL-DNN coefficient layout, per-leaf aux records summed into
     one ledger leg), Bernoulli participation with the force-one-client
     fallback (`participation`), the ξ gradient-refresh mask (`xi_mask`),
     the compressed model-stream downlink (`downlink_broadcast`), and the
     §2.3 coefficient layouts (`coeff_layout` — compact (n, r, r) blocks
     vs. full d×d) behind one (target_at, recon, ridge) interface.

  2. **Reducers** — the aggregation-backend axis.  All cross-client
     reductions (means/sums/maxes of Hessians, gradients, bit counts) go
     through a `Reducer` so the same method spec runs on two backends:

       * `VmapReducer`      — one device; the client axis is a plain leading
         array axis and reductions are `jnp.mean/sum/max(axis=0)`.
       * `ShardMapReducer`  — clients sharded over the mesh `data` axis
         inside `shard_map`; per-client state carries a leading local axis.
         `exact=True` (default) reduces by `all_gather` + the *identical*
         local reduction, which is bitwise-equal to the single-device
         backend (pinned by tests/test_sharding_multidev.py); `exact=False`
         reduces per the method's `ReducePlan` (`lax.psum/pmean/pmax` of
         locally pre-reduced partials), which is bandwidth-optimal but can
         differ in the last ulp (summation order).

     Specs batch a round's uplink legs through `Reducer.reduce_tree` (one
     collective per dtype instead of one per leg) and run server-only math
     — eigendecompositions, Newton solves — under `Reducer.once` (computed
     on shard 0 and broadcast by gather-and-select instead of replicated
     on every shard).  Both are bitwise-neutral restructurings; together
     they are what closed the sharded-vs-fast per-round gap.

  3. **Drivers** — jitted `lax.scan`s over rounds.  A `MethodSpec` (see
     `repro.core.specs`) supplies `prepare/init/step`; the drivers never
     know which algorithm they are running.  ONE chunked scan program
     per kind (`_chunk_body`: the stacked kind, and the cohort kind of
     `repro.core.cohort`) underlies every entry point — the carry is an
     explicit input/output, never donated, and per-round PRNG keys are
     explicit scan inputs:

       * `run_rounds`  — the batch driver (figure path): feeds its
         pre-split key array through one chunk (or one chunk per
         `StreamHook.every` rounds, emitting progress at chunk boundaries
         from the host — which is why streaming works on both backends).
       * `run_chunk` / `init_serve_carry` — the *service-loop* driver:
         rounds run in bounded chunks so control returns to the host
         between chunks (fault injection, checkpointing — see
         `repro.launch.fed_serve`, which drives them through
         `StackedEngine`).  Per-round keys are
         ``fold_in(root_key, t)`` of the absolute round index, so a
         trajectory is invariant to how rounds are batched into chunks —
         the crash-safe bit-exact-resume contract.

     The sharded backend wraps the same scan bodies in a single `shard_map`
     over the client mesh, so a whole sharded trajectory (or chunk) is
     still one SPMD program.  The carry itself crosses the shard_map
     boundary; `carry_client_flags` derives which carry leaves are
     client-stacked (the carry serialization contract — see
     `init_serve_carry`).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.rules import CLIENT_AXIS

from . import client_batch, comm, progcache


# ==========================================================================
# Reducers — the pluggable aggregation backend
# ==========================================================================
#: collective modes a `ReducePlan` can assign to an uplink payload class
_PLAN_MODES = ("gather", "psum", "pmean")
#: ops `Reducer.reduce_tree` understands, per leaf
_REDUCE_OPS = ("mean", "sum", "max")


@dataclasses.dataclass(frozen=True)
class ReducePlan:
    """Per-method collective-mode selection for the sharded reducer.

    Active only when ``ShardMapReducer(exact=False)``: each uplink leaf is
    classified by its payload rank (leaf shape minus the client axis —
    0 → ``scalar``, 1 → ``vector``, ≥2 → ``dense``) and reduced with the
    mode that class names:

      * ``"psum"``   — local pre-reduce + `lax.psum` in the mesh's fixed
        tree order (bandwidth-optimal; last-ulp summation-order drift);
      * ``"pmean"``  — local pre-mean + `lax.pmean` (same wire cost as
        psum; keeps magnitudes O(1) for f32 payloads);
      * ``"gather"`` — the exact-mode dataflow for just that class
        (all_gather + the identical local reduction, bitwise).

    ``exact=True`` ignores the mode fields — every leg gathers, which is
    what the cross-backend bitwise contract pins.  ``server_once`` gates
    `Reducer.once` (compute server-only math on shard 0, broadcast);
    ``fuse_uplink`` gates packing same-collective/same-dtype legs into one
    collective in `Reducer.reduce_tree`.  Both are bitwise-neutral — they
    are escape hatches for debugging, not parity knobs.

    Specs attach a plan as the ``MethodSpec.reduce_plan`` class attribute;
    the engine copies it onto the `ShardMapReducer` it builds."""

    dense: str = "psum"
    vector: str = "psum"
    scalar: str = "psum"
    server_once: bool = True
    fuse_uplink: bool = True

    def __post_init__(self):
        for f in ("dense", "vector", "scalar"):
            if getattr(self, f) not in _PLAN_MODES:
                raise ValueError(
                    f"ReducePlan.{f} must be one of {_PLAN_MODES}, "
                    f"got {getattr(self, f)!r}")

    def mode_for(self, payload_ndim: int) -> str:
        if payload_ndim == 0:
            return self.scalar
        if payload_ndim == 1:
            return self.vector
        return self.dense


@dataclasses.dataclass(frozen=True)
class Reducer:
    """Cross-client reduction interface.  `n` is the GLOBAL client count;
    per-client arrays seen by spec code always carry a leading `n_local`
    axis (== n on the vmap backend, n/ndev inside each shard otherwise)."""

    n: int

    #: devices the client axis spans (the sharded backend's field): a
    #: TPU program over more than one chip takes the server step's eigh
    #: path (`bl.proj_mu_route`)
    ndev = 1

    @property
    def n_local(self) -> int:
        raise NotImplementedError

    @property
    def n_total(self) -> int:
        """The FLEET size — the denominator for per-node bit accounting.

        Equal to `n` on the stacked backends (every client is materialized),
        but under cohort streaming (`CohortReducer`) `n` is the cohort
        capacity while `n_total` stays the global client count: per-node
        costs are amortized over the whole fleet, not the sampled cohort."""
        return self.n

    def mean(self, x: jax.Array) -> jax.Array:
        """(n_local, ...) → (...): mean over the global client axis."""
        raise NotImplementedError

    def sum(self, x: jax.Array) -> jax.Array:
        raise NotImplementedError

    def max(self, x: jax.Array) -> jax.Array:
        raise NotImplementedError

    def shard(self, x: jax.Array) -> jax.Array:
        """Slice a replicated (n, ...) array down to this shard's clients.

        Fleet-wide randomness (participation masks, per-client PRNG keys)
        is always drawn for all n clients from the replicated key and then
        sharded, so every backend sees the same per-client draws."""
        raise NotImplementedError

    def client_keys(self, key: jax.Array) -> jax.Array:
        """Per-client PRNG keys for this shard: (n_local, 2)."""
        return self.shard(jax.random.split(key, self.n))

    @jax.named_scope("reduce")
    def reduce_tree(self, tree, ops="mean"):
        """Reduce a whole uplink pytree across the fleet in one shot.

        ``ops`` is ``"mean" | "sum" | "max"`` applied to every leaf, or a
        matching pytree of those strings (one op per leaf).  Semantically
        identical to per-leaf `mean`/`sum`/`max` calls — bitwise so on the
        single-device backend and on the exact sharded backend — but the
        sharded reducer packs all leaves of the same (collective, dtype)
        group into ONE collective instead of one per leaf, which is where
        the per-round collective count collapses (see `ShardMapReducer`)."""
        ops_tree = (jax.tree.map(lambda _: ops, tree)
                    if isinstance(ops, str) else ops)

        def red(x, op):
            if op not in _REDUCE_OPS:
                raise ValueError(
                    f"reduce_tree op must be one of {_REDUCE_OPS}, got {op!r}")
            return getattr(self, op)(x)

        return jax.tree.map(red, tree, ops_tree)

    def tree_mean(self, tree):
        """`mean` mapped over a pytree of (n_local, ...) leaves — the
        cross-client reduction for pytree coefficient streams (BL-DNN)."""
        return self.reduce_tree(tree, "mean")

    def outer_mean(self, V):
        """``W ↦ mean_i W_iᵀ V_i`` for a constant client-stacked factor V:
        the fleet mean of per-client products from (n_local, r, d) factors
        as one contraction over (client, r), so the (n, d, d) stack of
        products never exists.  Call it outside the round loop: the
        sharded backend prepares V here, once per program.  The factors
        keep d as their minor axis: an r-wide minor axis would be padded
        to the TPU's 128 lanes."""
        return lambda W: jnp.einsum("nrd,nre->de", W, V) / self.n

    def tree_mean_presummed(self, tree, local_sums):
        """Fleet mean of client-stacked leaves given precomputed LOCAL
        client-axis sums (`local_sums`, payload-shaped — the extra output
        of a fused compress-then-reduce codec, see
        `repro.core.compressors.Compressor.compress_sum`).

        Backends that reduce exactly ignore ``local_sums`` and reduce
        ``tree`` itself (bitwise-identical to `tree_mean`); the
        bandwidth-optimal sharded path (``exact=False``) psums only the
        pre-summed compressed payloads — the collective moves one
        payload-sized tensor per dtype instead of the dense client stack."""
        del local_sums
        return self.tree_mean(tree)

    @jax.named_scope("server")
    def once(self, f: Callable, *args):
        """Run server-only math ``f(*args)`` once per fleet.

        On the single-device backend this is a plain call.  The sharded
        backend computes ``f`` on shard 0 only (the other shards' cores sit
        out instead of replicating the same eigendecomposition/solve ndev
        times) and broadcasts the result by gather-and-select — pure data
        movement, so the value every shard sees is bitwise the value the
        replicated computation would have produced.  ``f`` must be
        collective-free (inputs already reduced/replicated)."""
        return f(*args)


@dataclasses.dataclass(frozen=True)
class VmapReducer(Reducer):
    """Single-device backend: the client axis is a plain leading axis."""

    @property
    def n_local(self) -> int:
        return self.n

    @jax.named_scope("reduce")
    def mean(self, x):
        return jnp.mean(x, axis=0)

    @jax.named_scope("reduce")
    def sum(self, x):
        return jnp.sum(x, axis=0)

    @jax.named_scope("reduce")
    def max(self, x):
        return jnp.max(x, axis=0)

    def shard(self, x):
        return x


#: per-op local reduction over a gathered (n, ...) stack — the SAME ops
#: `VmapReducer` applies, which is what makes the exact path bitwise
_LOCAL_REDUCE = {
    "mean": lambda g: jnp.mean(g, axis=0),
    "sum": lambda g: jnp.sum(g, axis=0),
    "max": lambda g: jnp.max(g, axis=0),
}


@dataclasses.dataclass(frozen=True)
class ShardMapReducer(Reducer):
    """Mesh backend: clients sharded over `axis` inside `shard_map`.

    exact=True reduces by `all_gather` + the same local reduction as
    `VmapReducer` — bitwise-identical trajectories to the single-device
    fast path; `reduce_tree` packs every leaf of a dtype into ONE tiled
    gather (reshape/concat/split are pure data movement, so fusion is
    bitwise-neutral).  exact=False reduces per the method's `ReducePlan`
    (`lax.psum`/`pmean`/`pmax` of locally pre-reduced partials — less wire
    traffic, last-ulp summation-order differences)."""

    ndev: int = 1
    axis: str = CLIENT_AXIS
    exact: bool = True
    plan: ReducePlan = ReducePlan()

    @property
    def n_local(self) -> int:
        return self.n // self.ndev

    def _gather(self, x):
        return jax.lax.all_gather(x, self.axis, axis=0, tiled=True)

    @jax.named_scope("reduce")
    def mean(self, x):
        return self.reduce_tree(x, "mean")

    @jax.named_scope("reduce")
    def sum(self, x):
        return self.reduce_tree(x, "sum")

    @jax.named_scope("reduce")
    def max(self, x):
        return self.reduce_tree(x, "max")

    def shard(self, x):
        i = jax.lax.axis_index(self.axis)
        return jax.lax.dynamic_slice_in_dim(x, i * self.n_local, self.n_local, 0)

    # -------------------------------------------------- fused collectives
    def _gather_leaves(self, leaves):
        """All-gather a list of (n_local, ...) leaves as one tiled gather
        per dtype, returning the (n, ...) global stacks leaf-by-leaf.
        Reshape → concat → gather → split → reshape moves bits without
        arithmetic, so each returned stack is bitwise the stack a per-leaf
        `_gather` would have produced."""
        out = [None] * len(leaves)
        if not self.plan.fuse_uplink:
            for i, l in enumerate(leaves):
                out[i] = self._gather(l)
            return out
        by_dtype = {}
        for i, l in enumerate(leaves):
            by_dtype.setdefault(l.dtype, []).append(i)
        for idxs in by_dtype.values():
            flats = [leaves[i].reshape(self.n_local, -1) for i in idxs]
            widths = [f.shape[1] for f in flats]
            cat = flats[0] if len(flats) == 1 else jnp.concatenate(flats, axis=1)
            g = self._gather(cat)
            off = 0
            for i, w in zip(idxs, widths):
                out[i] = g[:, off:off + w].reshape(
                    (self.n,) + leaves[i].shape[1:])
                off += w
        return out

    def _fused_psum_like(self, entries):
        """One `psum`/`pmean` per (collective, dtype) group over a list of
        ``(index, collective, local_payload)`` entries; returns
        {index: reduced_payload}."""
        out = {}
        groups = {}
        for i, coll, v in entries:
            key = ((coll, v.dtype) if self.plan.fuse_uplink
                   else (coll, v.dtype, i))
            groups.setdefault(key, []).append((i, v))
        for key, items in groups.items():
            coll = key[0]
            flats = [v.reshape(-1) for _, v in items]
            cat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
            red = (jax.lax.pmean(cat, self.axis) if coll == "pmean"
                   else jax.lax.psum(cat, self.axis))
            off = 0
            for (i, v), f in zip(items, flats):
                out[i] = red[off:off + f.shape[0]].reshape(v.shape)
                off += f.shape[0]
        return out

    @jax.named_scope("reduce")
    def reduce_tree(self, tree, ops="mean"):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        op_list = ([ops] * len(leaves) if isinstance(ops, str)
                   else treedef.flatten_up_to(ops))
        for op in op_list:
            if op not in _REDUCE_OPS:
                raise ValueError(
                    f"reduce_tree op must be one of {_REDUCE_OPS}, got {op!r}")
        out = [None] * len(leaves)
        if self.exact:
            gathered = self._gather_leaves(leaves)
            for i, (op, g) in enumerate(zip(op_list, gathered)):
                out[i] = _LOCAL_REDUCE[op](g)
            return treedef.unflatten(out)
        entries, colls = [], {}
        for i, (l, op) in enumerate(zip(leaves, op_list)):
            if op == "max":
                out[i] = jax.lax.pmax(jnp.max(l, axis=0), self.axis)
                continue
            mode = self.plan.mode_for(l.ndim - 1)
            if mode == "gather":
                out[i] = _LOCAL_REDUCE[op](self._gather(l))
                continue
            # pmean of equal-sized local means IS the global mean; sums (and
            # means under a psum-mode plan) go up as local sums
            colls[i] = "pmean" if (mode == "pmean" and op == "mean") else "psum"
            loc = jnp.mean(l, axis=0) if colls[i] == "pmean" else jnp.sum(l, axis=0)
            entries.append((i, colls[i], loc))
        for i, red in self._fused_psum_like(entries).items():
            if colls[i] == "psum" and op_list[i] == "mean":
                red = red / self.n
            out[i] = red
        return treedef.unflatten(out)

    def outer_mean(self, V):
        # exact: contract the whole fleet in one fixed order, bitwise the
        # single-device contraction — the constant V is gathered here,
        # once, and each call gathers only W
        if self.exact:
            V = self._gather(V)
            return lambda W: jnp.einsum(
                "nrd,nre->de", self._gather(W), V) / self.n
        return lambda W: jax.lax.psum(
            jnp.einsum("nrd,nre->de", W, V), self.axis) / self.n

    @jax.named_scope("reduce")
    def tree_mean_presummed(self, tree, local_sums):
        if self.exact:
            return self.reduce_tree(tree, "mean")
        leaves, treedef = jax.tree_util.tree_flatten(local_sums)
        entries = []
        for i, s in enumerate(leaves):
            if self.plan.mode_for(s.ndim) == "pmean":
                entries.append((i, "pmean", s / self.n_local))
            else:
                entries.append((i, "psum", s))
        red = self._fused_psum_like(entries)
        out = [red[i] if coll == "pmean" else red[i] / self.n
               for i, coll, _ in entries]
        return treedef.unflatten(out)

    @jax.named_scope("server")
    def once(self, f: Callable, *args):
        if not self.plan.server_once:
            return f(*args)
        shapes = jax.eval_shape(f, *args)
        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        on_shard0 = jax.lax.axis_index(self.axis) == 0
        out = jax.lax.cond(on_shard0, lambda: f(*args), lambda: zeros)
        # broadcast by gather-and-select, NOT psum of a one-hot stack:
        # psum(x, 0, ..., 0) can flip the sign of -0.0, gather cannot
        return jax.tree.map(
            lambda o: jax.lax.all_gather(o, self.axis, axis=0,
                                         tiled=False)[0], out)


class CohortReducer:
    """Reducer view of a sampled cohort standing in for the whole fleet.

    Built INSIDE the cohort chunk program (it holds traced arrays, so it is
    never a jit argument): wraps an inner stacked `Reducer` sized to the
    cohort *capacity* c and presents the fleet to spec code so `MethodSpec.
    step` bodies run nearly verbatim:

      * ``n`` / ``n_local`` / ``shard`` / ``client_keys`` / ``once`` — the
        cohort axis (draw shapes, sharding) delegates to the inner reducer;
      * ``n_total`` — the GLOBAL fleet size, so ledger divisions and
        participation probabilities stay fleet-denominated;
      * ``idx`` — each slot's global client index (shard-local ``(n_local,)``
        int32), ``real`` — padding mask (capacity is padded to a multiple of
        the device count; padded slots hold garbage and must never reduce);
      * ``reduce_tree`` — fleet-wide aggregate from cohort rows plus the
        host-maintained ``frozen`` sums/maxes of the ABSENT clients' state
        (Alg. 2–3: a non-sampled client's shift state is frozen, so its
        contribution to Σᵢ Hᵢ etc. is exactly its epoch-start value, which
        the streaming engine maintains incrementally — see
        `repro.core.cohort`).  A ``mean`` aggregate with no frozen entry is
        delta-style (absent clients contribute exactly 0): only the cohort
        sum lands, still divided by ``n_total``.

    Bare ``mean``/``max`` are refused — an unnamed fleet reduction cannot
    be matched to a frozen statistic, and silently reducing over the cohort
    would be wrong math; cohort-capable specs route every fleet reduction
    through named `reduce_tree` dicts (or `once`-guarded server math).
    """

    is_cohort = True

    def __init__(self, inner: Reducer, idx: jax.Array, real: jax.Array,
                 frozen: dict, n_global: int):
        self.inner = inner
        self.idx = idx
        self.real = real
        self.frozen = frozen
        self.n_global = int(n_global)

    # ---- cohort axis (delegated) ------------------------------------------
    @property
    def n(self) -> int:
        return self.inner.n

    @property
    def n_local(self) -> int:
        return self.inner.n_local

    @property
    def n_total(self) -> int:
        return self.n_global

    @property
    def ndev(self) -> int:
        return self.inner.ndev

    def shard(self, x):
        return self.inner.shard(x)

    def client_keys(self, key):
        return self.inner.client_keys(key)

    @jax.named_scope("server")
    def once(self, f: Callable, *args):
        return self.inner.once(f, *args)

    def outer_mean(self, V):
        def unsupported(W):
            raise NotImplementedError(
                "CohortReducer cannot take a fleet mean of per-client "
                "products — absent clients keep no frozen sum for it")
        return unsupported

    # ---- fleet reductions --------------------------------------------------
    def _mask(self, x, fill):
        r = self.real.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.where(r, x, jnp.asarray(fill, x.dtype))

    @jax.named_scope("reduce")
    def sum(self, x):
        """Fleet sum of a cohort-supported quantity (absent clients are 0 by
        construction — participation masks, bit counts)."""
        return self.inner.sum(self._mask(x, 0))

    def mean(self, x):
        raise NotImplementedError(
            "CohortReducer cannot take an unnamed fleet mean — absent "
            "clients' contributions live in named frozen sums; use "
            "reduce_tree({'name': x}) (supports_cohort specs do)")

    def max(self, x):
        raise NotImplementedError(
            "CohortReducer cannot take an unnamed fleet max — use "
            "reduce_tree with a named leaf and a frozen fleet stat")

    @jax.named_scope("reduce")
    def reduce_tree(self, tree, ops="mean"):
        if not isinstance(tree, dict):
            raise NotImplementedError(
                "CohortReducer.reduce_tree needs a flat {name: leaf} dict "
                f"(frozen fleet stats are matched by name); got {type(tree)}")
        ops_d = ({name: ops for name in tree} if isinstance(ops, str)
                 else dict(ops))
        masked, inner_ops = {}, {}
        for name, leaf in tree.items():
            op = ops_d[name]
            if op not in _REDUCE_OPS:
                raise ValueError(
                    f"reduce_tree op must be one of {_REDUCE_OPS}, got {op!r}")
            masked[name] = self._mask(leaf, -jnp.inf if op == "max" else 0)
            inner_ops[name] = "max" if op == "max" else "sum"
        red = self.inner.reduce_tree(masked, inner_ops)
        out = {}
        for name, leaf in tree.items():
            op = ops_d[name]
            if op == "sum":
                out[name] = red[name]
            elif op == "mean":
                froz = self.frozen.get(name)
                s = red[name] if froz is None else froz + red[name]
                out[name] = s / self.n_total
            else:  # max
                if name not in self.frozen:
                    raise ValueError(
                        f"max-aggregate {name!r} needs a frozen fleet stat "
                        "(the absent clients' max) — the cohort engine "
                        "computes one per epoch")
                out[name] = jnp.maximum(self.frozen[name], red[name])
        return out

    def tree_mean(self, tree):
        raise NotImplementedError(
            "pytree coefficient streams (BL-DNN) are not cohort-capable yet")

    def tree_mean_presummed(self, tree, local_sums):
        raise NotImplementedError(
            "pytree coefficient streams (BL-DNN) are not cohort-capable yet")


def _cohort_participation(R: "CohortReducer", key: jax.Array, tau: int,
                          avail) -> Tuple[jax.Array, jax.Array]:
    """Participation over a sampled cohort: per-slot Bernoulli(τ/n_total)
    keyed by each slot's GLOBAL client index, so a client's draw for round t
    depends only on (round key, client id) — not its cohort slot, the
    cohort composition, or chunk boundaries.  Distributionally identical to
    the stacked fleet-wide draw restricted to the cohort, at O(c) cost.

    The force-one-client fallback picks the real slot with the minimum
    global index (a deterministic choice that is slot-order invariant).
    Fault injection is refused: availability masks are fleet-indexed and
    the streaming engine has no fleet on device to mask."""
    if avail is not None:
        raise ValueError(
            "cohort streaming does not support fault injection (avail must "
            "be None) — fault plans address the stacked fleet by index")
    tau = min(tau, R.n_total)
    k_mask, _ = jax.random.split(key)
    keys_i = jax.vmap(lambda i: jax.random.fold_in(k_mask, i))(R.idx)
    p = tau / R.n_total
    drawn = jax.vmap(lambda k: jax.random.bernoulli(k, p, ()))(keys_i)
    drawn = drawn & R.real
    n_surv = R.sum(drawn.astype(jnp.int32))
    # forced fallback: the real slot with the minimum global index, computed
    # as −max(−idx) (the reducer interface carries max, not min)
    big = jnp.iinfo(jnp.int32).max
    masked_idx = jnp.where(R.real, R.idx, big)
    gmin = -R.inner.reduce_tree({"i": -masked_idx}, "max")["i"]
    need = n_surv == 0
    part = drawn | (need & R.real & (R.idx == gmin))
    event = jnp.where(need, EVENT_FORCED, EVENT_NONE)
    return part, event.astype(jnp.int32)


# ==========================================================================
# Round context + degradation events
# ==========================================================================
#: `History.events` bit flags (per-round int32 bitmask, OR-combined).
EVENT_NONE = 0
#: faults shrank the round's surviving cohort below its τ target
EVENT_DEGRADED = 1
#: the force-one-client fallback engaged (empty cohort after the draw/faults)
EVENT_FORCED = 2
#: no client was available at all — the round stalls (nothing participates)
EVENT_ALL_DOWN = 4
#: the server step took the eigendecomposition of the μ-projection
#: (`bl.proj_mu_route`): a route, not a degradation
EVENT_EIGH = 8
#: the bits that mark a degraded round
EVENT_DEGRADATION = EVENT_DEGRADED | EVENT_FORCED | EVENT_ALL_DOWN


@dataclasses.dataclass
class RoundCtx:
    """Per-round traced context handed to `MethodSpec.step`.

    ``key`` is the round's PRNG key (replicated), ``t`` the absolute
    0-based round index, and ``avail`` an optional fleet-wide ``(n,)`` bool
    availability mask from the fault-injection layer (`repro.core.faults`)
    — ``None`` (the batch driver) means every client is reachable.
    ``avail`` is *fleet-wide and replicated* like the participation draws;
    spec code shards it through the `Reducer` where needed."""

    key: jax.Array
    t: jax.Array
    avail: "jax.Array | None" = None


def refresh_due(t, rounds_per_refresh: int):
    """Basis-refresh boundary predicate: True at rounds where an amortized
    basis shipment MAY re-ship (``t % T == 0`` for ``T ≥ 1``; never for
    ``T ≤ 0``, the ship-once policy).

    Deliberately a pure function of the ABSOLUTE round index `t` (a traced
    ``RoundCtx.t``), never of chunk-local position or wall clock — the same
    invariance contract as the per-round keys (``fold_in(root_key, t)``):
    fed_serve chunk boundaries and checkpoint resume cannot move a refresh
    round (pinned in tests/test_basis_ship.py, mirroring the cohort
    epoch-invariance pin)."""
    T = int(rounds_per_refresh)
    if T <= 0:
        return jnp.asarray(False)
    return (jnp.asarray(t) % T) == 0


# ==========================================================================
# Round-step combinators
# ==========================================================================
@jax.named_scope("compress")
def shift_update(compress: Callable, target: jax.Array, shift: jax.Array,
                 alpha: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One step of the compressed-difference shift recursion (Alg. 1 core):

        S = C(target − L),   L ← L + α·S.

    `compress` maps a delta tensor to (compressed_dense, aux) where aux is
    whatever the codec reports (message `Counts` for core compressors; the
    caller prices them via `comm.price`).  Returns (S, new_shift, aux).
    Contractive compressors use α = 1, unbiased ones α = 1/(ω+1).  This is
    the single mechanism shared by the GLM methods (Hessian-coefficient
    learning) and `repro.fed.bldnn` (gradient and Fisher-diagonal
    learning)."""
    S, aux = compress(target - shift)
    return S, shift + alpha * S, aux


def tree_shift_update(compress: Callable, target, shift,
                      alpha: float) -> Tuple[object, object, tuple]:
    """`shift_update` mapped over parameter *pytrees* (the BL-DNN layout):
    one compressed-difference recursion per leaf, aux records kept per leaf.

    Args:
      compress: ``compress(i, delta) -> (dense, aux)`` — compresses leaf
        ``i``'s client-stacked delta tensor.  The leaf index is a static
        Python int, so callers can close over per-leaf compressors (Top-K
        budgets scale with leaf size) and per-leaf PRNG keys.
      target, shift: pytrees of identical structure; leaves are
        client-stacked ``(n_local, ...)`` arrays.
      alpha: shared shift learning rate.

    Returns:
      ``(S, new_shift, auxs)`` — two pytrees shaped like the inputs plus a
      tuple of per-leaf aux records in leaf order (message `Counts` for the
      core compressors; price each against its compressor's wire and sum
      into ONE `comm.CommLedger` leg — per-leaf counts never grow their own
      billing scheme).
    """
    t_leaves, treedef = jax.tree_util.tree_flatten(target)
    s_leaves = jax.tree_util.tree_leaves(shift)
    if len(t_leaves) != len(s_leaves):
        raise ValueError(
            f"target/shift leaf mismatch: {len(t_leaves)} vs {len(s_leaves)}")
    outs = [shift_update(lambda d, i=i: compress(i, d), t, s, alpha)
            for i, (t, s) in enumerate(zip(t_leaves, s_leaves))]
    S = treedef.unflatten([o[0] for o in outs])
    new_shift = treedef.unflatten([o[1] for o in outs])
    return S, new_shift, tuple(o[2] for o in outs)


@jax.named_scope("compress")
def shift_update_sum(compress_sum: Callable, target: jax.Array,
                     shift: jax.Array, alpha: float):
    """`shift_update` through a fused compress-then-reduce codec.

    ``compress_sum`` maps a client-stacked delta to ``(dense, aux,
    local_sum)`` where ``local_sum == dense.sum(axis=0)`` (see
    `repro.core.compressors.Compressor.compress_sum` — under
    ``REPRO_BL_PALLAS=1`` Top-K fuses the selection and the partial sum
    into one kernel pass).  Returns ``(S, new_shift, aux, local_sum)``;
    feed the sum to `Reducer.tree_mean_presummed` so the bandwidth-optimal
    sharded path reduces the pre-summed payload instead of the stack."""
    S, aux, s_local = compress_sum(target - shift)
    return S, shift + alpha * S, aux, s_local


def tree_shift_update_sum(compress_sum: Callable, target, shift, alpha: float):
    """`tree_shift_update` through fused compress-then-reduce codecs:
    ``compress_sum(i, delta) -> (dense, aux, local_sum)`` per leaf.
    Returns ``(S, new_shift, auxs, local_sums)`` — the first two and last
    pytrees shaped like the inputs, auxs a tuple in leaf order."""
    t_leaves, treedef = jax.tree_util.tree_flatten(target)
    s_leaves = jax.tree_util.tree_leaves(shift)
    if len(t_leaves) != len(s_leaves):
        raise ValueError(
            f"target/shift leaf mismatch: {len(t_leaves)} vs {len(s_leaves)}")
    outs = [shift_update_sum(lambda d, i=i: compress_sum(i, d), t, s, alpha)
            for i, (t, s) in enumerate(zip(t_leaves, s_leaves))]
    S = treedef.unflatten([o[0] for o in outs])
    new_shift = treedef.unflatten([o[1] for o in outs])
    local_sums = treedef.unflatten([o[3] for o in outs])
    return S, new_shift, tuple(o[2] for o in outs), local_sums


def participation(R: Reducer, key: jax.Array, tau: int,
                  avail: "jax.Array | None" = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Bernoulli(τ/n) participation mask for this shard's clients, with the
    reference backend's force-one-client fallback (drawn fleet-wide from the
    replicated key, then sharded).

    τ is validated statically: τ < 1 raises `ValueError` (a Bernoulli(0)
    fleet would silently degenerate to the forced client every round) and
    τ > n clamps to full participation (bitwise-harmless: Bernoulli(p) with
    p ≥ 1 is always-true either way).

    ``avail`` is an optional fleet-wide ``(n,)`` bool availability mask from
    the fault layer (`RoundCtx.avail`): drawn participants that are down
    this round are removed, and when the surviving cohort is empty the
    fallback forces one *available* client instead.  ``avail`` of all-ones
    reproduces the unmasked path bitwise (mask and fallback index alike).

    Returns ``(mask, event)`` — the shard-local participation mask plus a
    replicated int32 `EVENT_*` bitmask for the round (`EVENT_DEGRADED` when
    faults pushed the cohort below τ, `EVENT_FORCED` when the fallback
    engaged, `EVENT_ALL_DOWN` when no client was available and the round
    stalls with an all-false mask).

    The mask and the fallback index come from SPLIT keys: reusing one key
    for both correlates the forced client with the mask draw (the reference
    backend mirrors this split, so parity stays bitwise)."""
    tau = int(tau)
    if tau < 1:
        raise ValueError(
            f"participation needs τ ≥ 1 expected clients per round, got "
            f"τ={tau} — pass τ in [1, n] (τ=n is full participation)")
    if getattr(R, "is_cohort", False):
        return _cohort_participation(R, key, tau, avail)
    tau = min(tau, R.n)
    k_mask, k_idx = jax.random.split(key)
    drawn = jax.random.bernoulli(k_mask, tau / R.n, (R.n,))
    idx = jax.random.randint(k_idx, (), 0, R.n)
    if avail is None:
        forced = ~drawn.any() & (jnp.arange(R.n) == idx)
        event = jnp.where(forced.any(), EVENT_FORCED, EVENT_NONE)
        return R.shard(drawn | forced), event.astype(jnp.int32)
    avail = jnp.asarray(avail, bool)
    n_avail = jnp.sum(avail)
    surviving = drawn & avail
    n_surv = jnp.sum(surviving)
    # fallback index rotated onto the available subset: with avail all-ones
    # cumsum(avail) == idx+1 first holds exactly at position idx, so the
    # masked path degenerates to the unmasked one bitwise
    pick = avail & (jnp.cumsum(avail) == idx % jnp.maximum(n_avail, 1) + 1)
    need_force = (n_surv == 0) & (n_avail > 0)
    part = surviving | (need_force & pick)
    event = (EVENT_DEGRADED * ((n_surv < jnp.sum(drawn)) & (n_surv < tau))
             + EVENT_FORCED * need_force
             + EVENT_ALL_DOWN * (n_avail == 0))
    return R.shard(part), event.astype(jnp.int32)


def xi_mask(R: Reducer, key: jax.Array, p: float) -> jax.Array:
    """Per-client ξ ~ Bernoulli(p) gradient-refresh mask (local slice)."""
    if p >= 1.0:
        return jnp.ones((R.n_local,), bool)
    return R.shard(jax.random.bernoulli(key, p, (R.n,)))


def xi_scalar(key: jax.Array, p: float) -> jax.Array:
    """Fleet-wide scalar ξ (BL1's single gradient-leg switch)."""
    if p >= 1.0:
        return jnp.asarray(True)
    return jax.random.bernoulli(key, p, (1,))[0]


@jax.named_scope("compress")
def downlink_broadcast(R: Reducer, comp, key: jax.Array, z: jax.Array,
                       x_target: jax.Array, eta: float, part: jax.Array):
    """Compressed model-stream downlink to participating clients:
    z_i ← z_i + η·C_i(x − z_i).  Returns (z_new, down_bits_per_node)."""
    v, counts = comp.compress(R.client_keys(key), x_target[None, :] - z)
    vbits = comm.price(comp.wire, counts)
    z_n = jnp.where(part[:, None], z + eta * v, z)
    return z_n, R.sum(jnp.where(part, vbits, 0.0)) / R.n_total


def global_grad(R: Reducer, batch, x: jax.Array) -> jax.Array:
    return R.mean(client_batch.grads(batch, x))

# NOTE: there is deliberately no in-scan global_loss combinator — specs emit
# evaluation iterates and the engine evaluates the whole trajectory outside
# the scan (`MethodSpec.eval_streams`, default `default_gap_stream`); an
# in-scan loss evaluation compiles differently under shard_map and would
# break the cross-backend bitwise contract.


# ==========================================================================
# Coefficient layouts (§2.3): block (n, r, r) vs full (n, d, d)
# ==========================================================================
@dataclasses.dataclass
class CoeffLayout:
    """How Hessian-coefficient state is laid out on this run.

    `target_at(z)` gives the per-client coefficient target h^i(∇²f_i(z)),
    `recon(S)` maps coefficient-space updates back to (n_local, d, d)
    Hessian space, `shape` is the local coefficient-state shape, and
    `ridge` is the analytic λI the server adds for data bases.  In block
    mode `recon_mean(S)` is the fleet mean of `recon(S)` taken from
    (n, r, d) factors, without the (n, d, d) stack; in the full layout it
    is None and callers reduce `recon(S)` with their other uplink leaves
    in one fused reduction."""

    target_at: Callable
    recon: Callable
    recon_mean: Optional[Callable]
    shape: Tuple[int, ...]
    ridge: jax.Array


@jax.named_scope("basis")
def coeff_layout(R: Reducer, batch, basisb, x0: jax.Array,
                 block: bool) -> CoeffLayout:
    d = batch.d
    lam = batch.lam
    # the layout's contractions run under the basis layer's scope wherever
    # a spec calls them
    basis = jax.named_scope("basis")
    if block:
        # §2.3 block mode (data basis only): state stays (n, r, r) and the
        # d×d data Hessian is never materialized (Γ = (AV)ᵀD(AV)/m).
        AV = client_batch.basis_AV(basisb, batch)
        rb = basisb.r_max
        Vt = jnp.swapaxes(basisb.V, 1, 2)           # (n, r, d)
        # mean_i V_i S_i V_iᵀ as one contraction of (n, r, d) factors: the
        # (n, d, d) reconstructions would not fit a chip at fig1-xl scale
        outer = R.outer_mean(Vt)
        return CoeffLayout(
            target_at=basis(lambda z: client_batch.hess_coeff_block(basisb, batch, z, AV)),
            recon=basis(lambda S: client_batch.reconstruct_block(basisb, S)),
            recon_mean=basis(lambda S: outer(jnp.einsum("nsr,nsd->nrd", S, Vt))),
            shape=(R.n_local, rb, rb),
            ridge=lam * jnp.eye(d, dtype=x0.dtype),
        )
    ridge = (lam * jnp.eye(d, dtype=x0.dtype)
             if basisb.kind == "data_outer" else jnp.zeros((d, d), x0.dtype))
    return CoeffLayout(
        target_at=basis(lambda z: client_batch.hess_coeff_target(basisb, batch, z)),
        recon=basis(basisb.reconstruct),
        recon_mean=None,
        shape=(R.n_local, d, d),
        ridge=ridge,
    )


# ==========================================================================
# Driver: one jitted scan over rounds, per (spec, reducer) pair
# ==========================================================================
@dataclasses.dataclass
class Env:
    """Per-run traced context handed to spec.init/step (not a scan carry)."""

    batch: object
    basisb: object
    x0: jax.Array
    extra: object  # spec-specific precomputation (e.g. a CoeffLayout)


@dataclasses.dataclass(frozen=True)
class StreamHook:
    """Mid-sweep instrumentation hook for long runs (`repro.exp` sweeps).

    The batch driver (`run_rounds`) splits its round budget into chunks of
    ``every`` rounds and emits ``callback(t, eval_x, ledger)`` from the
    host at each chunk boundary — ``t`` is the 0-based round index of the
    chunk's first round (so emissions land at t = 0, every, 2·every, ...),
    ``eval_x`` that round's evaluation iterate and ``ledger`` the
    cumulative per-leg `comm.CommLedger` at that round.  Because emission
    happens between chunk programs on the host, it works identically on
    BOTH aggregation backends — including `ShardMapReducer`, whose chunk
    outputs are replicated fleet-wide values, not shard-local ones.

    Emission is instrumentation only: the recorded `History` still comes
    from the full post-run gap evaluation, and chunking is bitwise-neutral
    (the chunk-size-invariance contract of the serve driver), so
    trajectories and gap streams are unchanged by attaching a hook.  Each
    distinct ``every`` compiles its own chunk program, so attach hooks to
    long runs, not micro-benches."""

    every: int
    callback: Callable

    def _emit(self, t, eval_x, ledger):
        self.callback(int(t), eval_x, ledger)


@jax.jit
def default_gap_stream(batch, xs_t, f_star):
    """f(x_t) − f* for a whole (steps, d) GLM trajectory in one vmapped
    pass — the default `MethodSpec.eval_streams` evaluation.

    Shared by both aggregation backends — same program + bitwise-identical
    iterates ⇒ bitwise-identical gap histories."""
    return jax.vmap(lambda x: jnp.mean(client_batch.losses(batch, x)))(xs_t) - f_star


def run_rounds(spec, batch, basisb, x0, f_star, keys, *,
               sharded: bool = False, exact: bool = True,
               stream: "StreamHook | None" = None):
    """Run `steps = len(keys)` rounds of `spec` and return the history
    streams ``(evals, CommLedger-of-streams)``: ``evals`` is the dict the
    spec's ``eval_streams`` hook derives from the trajectory (always
    containing ``"gap"``; pytree specs add extra named streams such as
    ``"loss"``), the ledger carries one per-leg bit stream per
    `comm.CommLedger` leg.

    sharded=False → `VmapReducer` on the default device.
    sharded=True  → `ShardMapReducer` over a 1-D client mesh spanning the
    most local devices that evenly divide the client count (a 1-device
    world still exercises the shard_map code path).  ``exact`` selects the
    bitwise gather path (default) vs the method's `ReducePlan` collectives.

    stream — optional `StreamHook`: the run is chunked every
    ``stream.every`` rounds and (round, eval_x, ledger) is emitted from
    each chunk boundary on the host.  Works on both backends.

    This is the chunked service-loop driver (`run_chunk`) under another
    entry point — one init program plus one scan program per chunk length,
    with per-round keys supplied explicitly (the batch path pre-splits
    them; the serve path derives them by `fold_in`)."""
    steps = int(keys.shape[0])
    init, chunk = _backend(spec, batch, basisb, x0, sharded, exact)
    carry = init(batch, basisb, x0)
    chunk_len = steps if stream is None else max(1, int(stream.every))
    parts = []
    t = 0
    while t < steps:
        s = min(chunk_len, steps - t)
        ts = jnp.arange(t, t + s)
        avail = jnp.ones((s, batch.n), bool)
        carry, ys = chunk(batch, basisb, x0, carry, ts, keys[t:t + s], avail)
        if stream is not None:
            # row 0 of the chunk = round t's iterate + cumulative ledger
            stream._emit(ts[0], ys[0][0], jax.tree.map(lambda a: a[0], ys[1]))
        parts.append(ys)
        t += s
    if len(parts) == 1:
        xs_t, leds, _events = parts[0]
    else:
        xs_t, leds, _events = jax.tree.map(
            lambda *a: jnp.concatenate(a, axis=0), *parts)
    # ys = (eval_x (steps, d), CommLedger of (steps,) per-leg streams,
    # events (steps,) int32 EVENT_* bitmasks — all-zero without a fault
    # layer, so the batch path drops them).
    if sharded:
        # outputs come back committed to the client mesh; rehome them so the
        # gap evaluation below is the same default-device program on every
        # backend (this is what makes the histories bitwise-comparable)
        import numpy as np

        xs_t, leds = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)),
                                  (xs_t, leds))
    evals = spec.eval_streams(batch, xs_t, f_star)
    return evals, leds


# ==========================================================================
# Chunked service-loop driver (repro.launch.fed_serve)
# ==========================================================================
# Retrace audit — every trace of a dispatch-path program body bumps a
# counter.  The invariant the audit pins (tests/test_retrace_audit.py):
# ONE trace per (spec, shapes) per process and ZERO retraces across
# chunk/epoch boundaries, on every backend — so the dispatch-cost
# regressions PR 7 closed (a retrace costs ~1000× the compiled per-round
# dispatch) can never silently return.  Shape-only evaluations
# (`carry_client_flags` runs `spec.init` under `jax.eval_shape` twice) are
# tagged with a "/shape_eval" suffix so real retraces stand out.
_TRACE_COUNTS: collections.Counter = collections.Counter()
_IN_SHAPE_EVAL = False


def _note_trace(kind: str) -> None:
    _TRACE_COUNTS[kind + "/shape_eval" if _IN_SHAPE_EVAL else kind] += 1


def trace_counts() -> dict:
    """Snapshot of {program kind: trace count} since the last reset.
    Kinds: "init", "chunk", "cohort_chunk" (+ "/shape_eval" variants)."""
    return dict(_TRACE_COUNTS)


def reset_trace_audit() -> None:
    _TRACE_COUNTS.clear()


def _with_client_dim(tree, n_new: int):
    """Abstract (shape-only) copy of a client-stacked pytree with the
    leading client axis resized — every leaf of `ClientBatch` /
    `BatchedBasis` / `TreeBatch` carries the client axis first (static aux
    like ``lam`` is not a leaf and survives unflattening untouched)."""
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((n_new,) + tuple(l.shape[1:]),
                                       l.dtype), tree)


def _init_body(spec, R: Reducer, batch, basisb, x0):
    _note_trace("init")
    env = Env(batch=batch, basisb=basisb, x0=x0,
              extra=spec.prepare(R, batch, basisb, x0))
    return spec.init(R, env)


_init_jit = functools.partial(jax.jit, static_argnames=("spec", "R"))(_init_body)


def carry_client_flags(spec, batch, basisb, x0):
    """Which carry leaves are client-stacked — the carry serialization /
    sharding contract for the chunked driver.

    Derived structurally, with no per-spec declarations: `spec.init` is
    shape-evaluated twice (at n and at 2n clients) and exactly the leaves
    whose shape moved carry the client axis.  This disambiguates d == n
    coincidences and works for any spec the engine can run.  Returns a
    bool pytree shaped like the carry."""
    n = batch.n

    def init_at(b, bb, nn):
        return _init_body(spec, VmapReducer(n=nn), b, bb, x0)

    global _IN_SHAPE_EVAL
    _IN_SHAPE_EVAL = True
    try:
        s1 = jax.eval_shape(functools.partial(init_at, nn=n), batch, basisb)
        b2 = _with_client_dim(batch, 2 * n)
        bb2 = (basisb if basisb is None
               or getattr(spec, "basis_replicated", False)
               else _with_client_dim(basisb, 2 * n))
        s2 = jax.eval_shape(functools.partial(init_at, nn=2 * n), b2, bb2)
    finally:
        _IN_SHAPE_EVAL = False
    return jax.tree.map(lambda a, b: a.shape != b.shape, s1, s2)


def _flags_key(flags):
    """Hashable (leaves, treedef) form of a carry-flags pytree — the cache
    key for the per-(spec, reducer, mesh) sharded chunk programs."""
    leaves, treedef = jax.tree_util.tree_flatten(flags)
    return tuple(leaves), treedef


def _abstract_sig(*trees):
    """Hashable shape/dtype signature of arbitrary pytrees — everything
    `carry_client_flags` (a pure shape evaluation) can depend on."""
    leaves, treedef = jax.tree_util.tree_flatten(trees)
    return treedef, tuple(
        (np.shape(l), str(np.result_type(getattr(l, "dtype", type(l)))))
        for l in leaves)


# carry_client_flags costs two full Python traces of spec.init — ~15ms on a
# mid-size GLM spec, which used to be paid per init_serve_carry AND per
# run_chunk dispatch (it dwarfed the ~4ms compiled sharded program and was
# most of the sharded backend's fixed per-call overhead).  The flags are a
# pure function of (spec, abstract shapes), so memoize on that signature.
_FLAGS_CACHE: dict = {}


def _carry_flags_key_cached(spec, batch, basisb, x0):
    key = (spec, _abstract_sig(batch, basisb, x0))
    fk = _FLAGS_CACHE.get(key)
    if fk is None:
        fk = _FLAGS_CACHE[key] = _flags_key(
            carry_client_flags(spec, batch, basisb, x0))
    return fk


def _chunk_body(spec, R: Reducer, batch, basisb, x0, carry, ts, keys, avail,
                cidx=None, creal=None, frozen=None, *, n_global=None):
    """One chunk of rounds: a scan of ``spec.step`` over ``(ts, keys,
    avail)``, the carry in and out.

    The stacked kind (``n_global`` None) shows spec code the fleet's
    reducer ``R``.  The cohort kind wraps the cohort-capacity reducer ``R``
    in a `CohortReducer` over the slots' global client indices ``cidx``,
    their padding mask ``creal`` and the absent clients' ``frozen`` fleet
    statistics, all constant for the chunk (the cohort engine cuts chunks
    at epoch boundaries); it takes no availability schedule (``avail``
    None)."""
    if n_global is None:
        _note_trace("chunk")
    else:
        _note_trace("cohort_chunk")
        R = CohortReducer(inner=R, idx=cidx, real=creal, frozen=frozen,
                          n_global=n_global)
    env = Env(batch=batch, basisb=basisb, x0=x0,
              extra=spec.prepare(R, batch, basisb, x0))

    def step(carry, xt):
        t, key_t, avail_t = xt
        return spec.step(R, env, carry, RoundCtx(key=key_t, t=t,
                                                 avail=avail_t))

    return jax.lax.scan(step, carry, (ts, keys, avail))


# the one single-device chunk program of both kinds.  It donates nothing:
# executables that came back through serialize/deserialize mishandle
# donated carry buffers once calls are CHAINED through engine state
# (outputs aliased into donated memory feed the next call) — outputs go
# bitwise-wrong with bitwise-identical inputs.  Donation never changes
# values, so one donation-free program keeps a cache hit == a miss ==
# the uncached call bitwise, at one in-flight carry copy per chunk call.
_chunk_program = functools.partial(
    jax.jit, static_argnames=("spec", "R", "n_global"))(_chunk_body)


# --------------------------------------------------------------------------
# AOT program dispatch (repro.core.progcache tier 1)
# --------------------------------------------------------------------------
# resolved executables, keyed (kind, spec, backend scope, abstract arg sig)
# — module-level so the memo survives `_backend`'s per-dispatch wrapper
# construction (a closure-held memo would be rebuilt every call)
_AOT_PROGS: dict = {}


def clear_aot_memo() -> None:
    """Drop the in-process executable memo (tests use this to force the
    next dispatch back through the on-disk cache)."""
    _AOT_PROGS.clear()


class _AotProgram:
    """One serve program, ``fn(*static, *args, **static_kw)`` for a jitted
    ``fn``, behind cache-aware dispatch.

    With no active `progcache` cache, ``__call__`` calls ``fn``.  With a
    cache active, the first call per abstract argument signature resolves
    an AOT executable — deserialized from disk on a hit, compiled from
    ``fn``'s own lowering on any miss and persisted — and every later call
    reuses it.  ``fn`` donates nothing (see `_chunk_program`), so the two
    paths are bitwise the same program.

    ``resolve`` is the execution-free half (lower/load only): the serve
    loop warms programs through it *before* checkpoint restore, which is
    what moves compile latency out of time-to-first-round."""

    def __init__(self, kind: str, spec, scope: tuple, fn: Callable,
                 *static, **static_kw):
        self.kind = kind
        self._spec = spec
        self._scope = scope
        self._fn = fn
        self._static = static
        self._static_kw = static_kw

    def resolve(self, *args):
        """The compiled executable for these (concrete) args, or None when
        no cache is active.  Never executes the program."""
        cache = progcache.active()
        if cache is None:
            return None
        sig = _abstract_sig(*args)
        memo_key = (self.kind, self._spec, self._scope, sig)
        prog = _AOT_PROGS.get(memo_key)
        if prog is None:
            prog, _ = cache.load_or_compile(
                name=self.kind,
                key_parts=(self.kind, progcache.source_digest(),
                           progcache.fingerprint(self._spec),
                           progcache.fingerprint(self._scope), repr(sig)),
                lower=lambda: self._fn.lower(*self._static, *args,
                                             **self._static_kw),
                aux={"scope": [str(s) for s in self._scope]})
            _AOT_PROGS[memo_key] = prog
        return prog

    def __call__(self, *args):
        prog = self.resolve(*args)
        if prog is None:
            return self._fn(*self._static, *args, **self._static_kw)
        return prog(*args)


def _vmap_init_program(spec, R: Reducer) -> _AotProgram:
    return _AotProgram("serve_init", spec, ("vmap", R.n), _init_jit, spec, R)


def serve_init(spec, R: Reducer, batch, basisb, x0):
    """The single-device init program under AOT dispatch — shared by the
    stacked serve backend and the cohort engine's fleet initialisation
    (`repro.core.cohort._init_fleet`), so both populate the same cache
    entries."""
    return _vmap_init_program(spec, R)(batch, basisb, x0)


@functools.lru_cache(maxsize=None)
def _sharded_programs(spec, R: "ShardMapReducer", mesh, flags_key, n_global):
    """Jitted shard_map (init, chunk) programs whose carry crosses the
    shard_map boundary: client-stacked carry leaves shard over the mesh,
    everything else is replicated (per `carry_client_flags`).  The cohort
    kind (``n_global`` set) shards the COHORT axis in the client axis's
    place and takes three more arguments: the slots' global indices and
    padding mask shard with it, the frozen fleet aggregates are replicated
    server state (every shard needs them to finish a fleet mean/max)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import CLIENT_AXIS, client_chunk_specs

    leaves, treedef = flags_key
    carry_specs = jax.tree_util.tree_unflatten(
        treedef, [P(CLIENT_AXIS) if f else P() for f in leaves])
    in_specs, out_specs = client_chunk_specs(
        carry_specs,
        basis_replicated=getattr(spec, "basis_replicated", False))
    if n_global is not None:
        in_specs += (P(CLIENT_AXIS), P(CLIENT_AXIS), P())
    init = jax.jit(jax.shard_map(
        functools.partial(_init_body, spec, R), mesh=mesh,
        in_specs=in_specs[:3], out_specs=carry_specs, check_vma=False))
    chunk = jax.jit(jax.shard_map(
        functools.partial(_chunk_body, spec, R, n_global=n_global),
        mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False))
    return init, chunk


def _backend(spec, batch, basisb, x0, sharded: bool, exact: bool,
             n_global: Optional[int] = None):
    """The (init, chunk) programs of one chunk kind: the stacked kind
    (``n_global`` None) over the fleet in ``batch``, or the cohort kind
    over ``batch.n`` cohort slots of a fleet of ``n_global`` clients."""
    kind = "serve_chunk" if n_global is None else "cohort_chunk"
    if not sharded:
        R = VmapReducer(n=batch.n)
        scope = ("vmap", R.n if n_global is None else n_global)
        return (_vmap_init_program(spec, R),
                _AotProgram(kind, spec, scope, _chunk_program, spec, R,
                            n_global=n_global))
    from repro.launch.mesh import make_client_mesh
    from repro.sharding.rules import mesh_fingerprint

    mesh, ndev = make_client_mesh(batch.n)
    R = ShardMapReducer(n=batch.n, ndev=ndev, exact=exact,
                        plan=getattr(spec, "reduce_plan", ReducePlan()))
    fk = _carry_flags_key_cached(spec, batch, basisb, x0)
    init, chunk = _sharded_programs(spec, R, mesh, fk, n_global)
    scope = (("shmap", ndev, exact, mesh_fingerprint(mesh))
             + (() if n_global is None else (n_global,)))
    return (_AotProgram("serve_init", spec, scope, init),
            _AotProgram(kind, spec, scope, chunk))


def init_serve_carry(spec, batch, basisb, x0, *, sharded: bool = False,
                     exact: bool = True):
    """The round-0 scan carry as an explicit (global) pytree — the state the
    service loop checkpoints.  Its structure and leaf shapes/dtypes ARE the
    carry serialization contract: `repro.exp.artifacts.save_checkpoint`
    stores the flattened leaves and restore validates them against a fresh
    `init_serve_carry` shape evaluation, so an incompatible spec change
    fails loudly instead of resuming garbage."""
    init, _ = _backend(spec, batch, basisb, x0, sharded, exact)
    return init(batch, basisb, x0)


def chunk_program(spec, batch, basisb, x0, carry, t0: int, steps: int,
                  root_key, *, avail=None, cohort=None, sharded: bool = False,
                  exact: bool = True):
    """The chunk program for rounds [t0, t0 + steps) and its arguments:
    ``program(*args)`` runs the rounds, ``program.resolve(*args)`` only
    loads or compiles the program (the engines warm through it before
    checkpoint restore).

    Per-round keys are ``fold_in(root_key, t)`` of the absolute round
    index.  ``cohort`` None is the stacked kind, whose ``avail`` is an
    optional ``(steps, n)`` availability schedule (all-ones by default,
    bitwise no fault layer at all).  ``cohort = (cidx, creal, frozen,
    n_global)`` is the cohort kind: ``batch`` and ``carry`` hold the cohort
    slots, ``cidx`` their global client indices, ``creal`` their padding
    mask and ``frozen`` the fleet statistics of the epoch's ABSENT
    clients; it takes no schedule."""
    ts = jnp.arange(t0, t0 + steps)
    # the fold_in happens outside the scan (vmapped over the chunk's round
    # indices — threefry is elementwise, so this is bitwise the in-scan
    # per-round fold_in) so the scan body takes explicit keys: the batch
    # driver feeds the same program its pre-split key array instead
    keys = jax.vmap(lambda t: jax.random.fold_in(root_key, t))(ts)
    if cohort is not None:
        cidx, creal, frozen, n_global = cohort
        _, prog = _backend(spec, batch, basisb, x0, sharded, exact,
                           int(n_global))
        return prog, (batch, basisb, x0, carry, ts, keys, None,
                      jnp.asarray(cidx, jnp.int32), jnp.asarray(creal, bool),
                      frozen)
    if avail is None:
        avail = jnp.ones((steps, batch.n), bool)
    avail = jnp.asarray(avail, bool)
    if avail.shape != (steps, batch.n):
        raise ValueError(
            f"avail schedule must be (steps, n) = ({steps}, {batch.n}), "
            f"got {avail.shape}")
    _, prog = _backend(spec, batch, basisb, x0, sharded, exact)
    return prog, (batch, basisb, x0, carry, ts, keys, avail)


def run_chunk(spec, batch, basisb, x0, carry, t0: int, steps: int, root_key,
              *, avail=None, sharded: bool = False, exact: bool = True):
    """Run `steps` rounds starting at absolute round `t0` from an explicit
    carry; returns ``(carry, (eval_x stream, CommLedger of per-leg streams,
    events stream))`` with the new carry ready for the next chunk (or for a
    checkpoint).

    Per-round keys are ``fold_in(root_key, t)`` — a pure function of the
    absolute round index — so a trajectory is invariant to chunk boundaries
    and a run resumed from a checkpoint at any boundary is bit-exactly the
    uninterrupted run.  ``avail`` is an optional ``(steps, n)`` bool
    availability schedule from the fault layer (`repro.core.faults`); rows
    reach specs as `RoundCtx.avail`.  An all-ones schedule (the default) is
    bitwise-equivalent to no fault layer at all.

    The program donates nothing (see `_chunk_program`): the input carry
    stays valid, and a caller that goes on from the returned carry holds
    one more carry only for the length of the call.

    Chunk programs compile once per (spec, backend, chunk length); the
    service loop reuses one length for every full chunk, so only a trailing
    partial chunk costs a second compile."""
    prog, args = chunk_program(spec, batch, basisb, x0, carry, t0, steps,
                               root_key, avail=avail, sharded=sharded,
                               exact=exact)
    return prog(*args)


class StackedEngine:
    """The whole fleet on the device, behind the engine contract the serve
    loop drives (`repro.core.cohort.CohortEngine` is the other engine):
    `init_serve_carry` and `run_chunk` over one client-stacked batch, with
    per-round keys ``fold_in(root_key, t)``.

    The engine owns the carry.  Its first `carry_template` runs the init
    program once; a fresh run goes on from that carry itself and `restore`
    replaces it, so the device never holds a template beside the carry.
    Each chunk's carry is a new buffer (nothing is donated), and the engine
    drops the old one."""

    def __init__(self, spec, batch, basisb, x0, root_key, *,
                 sharded: bool = False):
        self.spec = spec
        self.batch = batch
        self.basisb = basisb
        self.x0 = x0
        self.root_key = root_key
        self.sharded = bool(sharded)
        self._carry = None

    def carry_template(self):
        """The carry the next chunk starts from: the round-0 carry (the
        first call runs the init program) until a chunk or `restore`
        replaces it.  Its structure and leaf shapes are the serialization
        contract checkpoints are validated against."""
        if self._carry is None:
            self._carry = init_serve_carry(
                self.spec, self.batch, self.basisb, self.x0,
                sharded=self.sharded)
        return self._carry

    def warm_programs(self, chunk: int) -> bool:
        """Resolve the program of a ``chunk``-round chunk — load it from the
        active program cache or compile and persist it — without running a
        round.  Returns False when no cache is active."""
        if progcache.active() is None:
            return False
        prog, args = chunk_program(
            self.spec, self.batch, self.basisb, self.x0,
            self.carry_template(), 0, int(chunk), self.root_key,
            sharded=self.sharded)
        prog.resolve(*args)
        return True

    def run_chunk(self, t0: int, steps: int, avail=None):
        """Run rounds [t0, t0 + steps) (``avail``: `run_chunk`'s schedule)
        and return their streams ``(eval_x, CommLedger of per-leg streams,
        events)``."""
        self._carry, ys = run_chunk(
            self.spec, self.batch, self.basisb, self.x0,
            self.carry_template(), t0, steps, self.root_key, avail=avail,
            sharded=self.sharded)
        return ys

    def checkpoint_view(self):
        """(carry leaves, host state) for `artifacts.save_checkpoint`: the
        device carry pulled to the host; the stacked engine keeps no host
        state (None)."""
        return [np.asarray(leaf)
                for leaf in jax.tree_util.tree_leaves(self._carry)], None

    def restore(self, t: int, carry, host_state=None):
        """Adopt a checkpoint's carry (validated and unflattened by the
        caller) as the carry round ``t`` starts from."""
        del t, host_state
        self._carry = carry

    def serve_meta(self) -> dict:
        """The serve record's ``meta`` keys of this engine: none."""
        return {}

    def close(self):
        pass
