"""Cohort-streaming engine: flat-in-n federated rounds for ≥100k clients.

The stacked engine (`repro.core.rounds.run_chunk`) materializes every
client's data and shift state on device, so the fleet size n is bounded by
accelerator memory — fig1-xl tops out at 512 clients.  The paper's
partial-participation methods (BL2/BL3, Alg. 2–3) and the Bernoulli-lazy
uplink (FedNL-BAG) only ever *touch* the sampled cohort, so this module
streams instead:

  * the full fleet lives in a host-resident `client_batch.ClientStore`
    (data plane A/b plus the per-client carry leaves — shifts z_i/w_i,
    Hessian estimates L_i, ...);
  * per **epoch** (``rounds_per_cohort`` consecutive rounds) a cohort of
    ``cohort`` clients is sampled by a counter-based host PRNG keyed on
    (root key, epoch) — a pure function of the absolute epoch index, so
    the schedule is invariant to how rounds are batched into chunks,
    exactly like the serve driver's ``fold_in(root_key, t)`` round keys;
  * only the cohort's rows are gathered onto the device and run through
    the cohort kind of the chunk program (`rounds.chunk_program`), with
    the next epoch's gather + host→device transfer **double-buffered** on
    a prefetch thread behind the current chunk's jitted scan;
  * absent clients' state stays frozen per Alg. 2–3 — their contribution
    to each fleet aggregate (Σᵢ Hᵢ, Σᵢ gᵢ, max βᵢ ...) is maintained
    *incrementally* on the host (`MethodSpec.cohort_aggregates`): per
    epoch the engine subtracts the cohort's epoch-start rows from the
    running fleet totals to get the ``frozen`` contribution, and adds the
    updated rows back at epoch end.  Per-round work is therefore O(cohort),
    not O(n) — per-round wall time is flat in the fleet size (the
    ``cohort_stream`` bench read ≤1.15× from n=1k to n=100k, a CPU
    timing from before the chip).

The engine needs a cohort smaller than the fleet.  A store-backed cell
whose cohort covers the fleet runs on `rounds.StackedEngine` over the
whole fleet gathered onto the device: `repro.exp.engine.make_engine` is
the one place that chooses, and the serve loop drives either engine
through the same contract (`carry_template`, `warm_programs`,
`run_chunk`, `checkpoint_view`, `restore`, `close`).

Checkpointing: the device carry (cohort rows + server state) is the usual
flattened-leaves payload; the host side (store state, aggregate totals,
the current epoch's frozen stats) rides in the ``repro.exp/ckpt@2``
``host_state`` payload (`repro.exp.artifacts.save_checkpoint`).  Restoring
at round t resamples the epoch's cohort deterministically and resumes
bit-exactly mid-epoch or at a boundary.
"""
from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import client_batch, rounds, spans

#: fold_in salt separating the cohort-sampler stream from the per-round
#: key stream (rounds use fold_in(root_key, t) with small t)
COHORT_SALT = 0x0C0407


def standard_basisb(d: int, n: int) -> client_batch.BatchedBasis:
    """A leafless standard-basis `BatchedBasis` for n clients — the basis
    kind of the store-backed problems (no per-client arrays to stream)."""
    return client_batch.BatchedBasis(kind="standard", d=d, rs=(d,) * n)


# ==========================================================================
# Host-side (numpy) fleet evaluation — slab-wise, never O(n) on device
# ==========================================================================
def store_loss(store: client_batch.ClientStore, x, slab: int = 8192) -> float:
    """Global logistic loss over the full fleet, slab-accumulated in f64 on
    the host (matches `client_batch.global_loss` / `glm` conventions:
    mean-over-clients of mean-over-samples logaddexp(0, −b·Ax) + λ/2‖x‖²)."""
    x = np.asarray(x, np.float64)
    tot = 0.0
    for lo in range(0, store.n, slab):
        A = np.asarray(store.A[lo:lo + slab], np.float64)
        b = np.asarray(store.b[lo:lo + slab], np.float64)
        z = np.einsum("nmd,d->nm", A, x) * b
        tot += float(np.sum(np.mean(np.logaddexp(0.0, -z), axis=1)))
    return tot / store.n + 0.5 * store.lam * float(np.dot(x, x))


def store_newton_solve(store: client_batch.ClientStore, x0, iters: int = 20,
                       slab: int = 8192) -> np.ndarray:
    """Reference optimum of the store's fleet objective by damped-free
    Newton, with the gradient/Hessian accumulated slab-by-slab on the host
    (the stacked `newton_solve_fused` would need the whole (n, m, d) fleet
    on device — infeasible at streaming scale)."""
    x = np.asarray(x0, np.float64).copy()
    d = store.d
    for _ in range(int(iters)):
        g = np.zeros(d)
        H = np.zeros((d, d))
        for lo in range(0, store.n, slab):
            A = np.asarray(store.A[lo:lo + slab], np.float64)
            b = np.asarray(store.b[lo:lo + slab], np.float64)
            z = np.einsum("nmd,d->nm", A, x) * b
            s = 1.0 / (1.0 + np.exp(z))          # σ(−z)
            m = A.shape[1]
            g += np.einsum("nmd,nm->d", A, -b * s) / m
            H += np.einsum("nmd,nm,nme->de", A, s * (1.0 - s), A) / m
        g = g / store.n + store.lam * x
        H = H / store.n + store.lam * np.eye(d)
        x = x - np.linalg.solve(H, g)
    return x


# ==========================================================================
# Slab-wise fleet init programs
# ==========================================================================
@functools.partial(jax.jit, static_argnames=("spec", "R"))
def _slab_extras(spec, R, batch, basisb, x0, carry):
    """`MethodSpec.cohort_init_extras` for one slab (separate program from
    the init itself, so that the init is the EXACT stacked
    `rounds._init_jit` program)."""
    env = rounds.Env(batch=batch, basisb=basisb, x0=x0,
                     extra=spec.prepare(R, batch, basisb, x0))
    return spec.cohort_init_extras(R, env, carry)


class CohortEngine:
    """Streaming round driver over a `ClientStore`.

    Args:
      spec: a ``supports_cohort`` `MethodSpec` (BL2/BL3/FedNL-BAG).
      store: the host-resident fleet (`client_batch.ClientStore`); its
        ``state`` plane is (re)initialized by the engine.
      x0: initial iterate (d,).
      cohort: clients sampled per epoch, at least 1 and fewer than
        ``store.n`` (a cohort that covers the fleet is the stacked
        engine's job).
      rounds_per_cohort: rounds a sampled cohort stays resident (the epoch
        length); higher amortizes the gather, lower refreshes participation
        across more of the fleet.
      root_key: the run's root PRNG key — per-round keys are
        ``fold_in(root_key, t)``, the sampler stream is
        ``fold_in(root_key, COHORT_SALT)``.
      basis: ``"standard"`` or None (BL3) — store-backed problems use
        convention bases only (nothing per-client to ship or stream).
      sharded: run chunks through the shard_map backend (the cohort axis
        shards over the client mesh); capacity is padded to a multiple of
        the device count.
      prefetch: double-buffer the next epoch's gather + H2D transfer on a
        background thread (pure data movement — bitwise-neutral).
    """

    def __init__(self, spec, store: client_batch.ClientStore, x0, *,
                 cohort: int, rounds_per_cohort: int, root_key,
                 basis: Optional[str] = "standard", sharded: bool = False,
                 slab: int = 4096, prefetch: bool = True):
        if rounds_per_cohort < 1:
            raise ValueError(
                f"rounds_per_cohort must be >= 1, got {rounds_per_cohort}")
        if cohort < 1:
            raise ValueError(f"cohort must be >= 1, got {cohort}")
        self.spec = spec
        self.store = store
        self.x0 = jnp.asarray(x0)
        self.n = store.n
        self.d = int(self.x0.shape[0])
        self.rpc = int(rounds_per_cohort)
        self.root_key = root_key
        self.sharded = bool(sharded)
        self.slab = int(slab)
        self.cohort = int(cohort)
        if not getattr(spec, "supports_cohort", False):
            raise ValueError(
                f"{type(spec).__name__} is not cohort-capable "
                "(MethodSpec.supports_cohort is False) — absent clients' "
                "fleet contributions cannot be frozen; run it stacked")
        if basis not in (None, "standard"):
            raise ValueError(
                f"cohort streaming supports the 'standard' convention basis "
                f"or None, got {basis!r} (per-client basis arrays would "
                "have to stream with the cohort — not implemented)")
        if self.cohort >= self.n:
            raise ValueError(
                f"cohort {self.cohort} covers the fleet of {self.n}: run "
                "it on the stacked engine (rounds.StackedEngine, which "
                "repro.exp.engine.make_engine picks for such a cell)")
        # padded capacity: every shard holds the same number of slots
        cap = self.cohort
        if self.sharded:
            ndev = jax.local_device_count()
            cap = ((cap + ndev - 1) // ndev) * ndev
        self.cap = cap
        self._basis_kind = basis
        self._basis_cap = (None if basis is None
                           else standard_basisb(self.d, self.cap))
        self._seed64 = self._sampler_seed()
        self._aggs = dict(spec.cohort_aggregates())
        self._totals: dict = {}
        self._server: dict = {}
        self._cur: Optional[dict] = None
        self._treedef = None
        self._is_client = None
        self._names = None
        self.metrics = {"prefetch_wait_us": 0.0, "prefetch_work_us": 0.0,
                        "h2d_bytes": 0, "epochs_prefetched": 0,
                        "epochs_loaded": 0}
        self._prefetch_on = bool(prefetch)
        self._pool = (ThreadPoolExecutor(max_workers=1)
                      if self._prefetch_on else None)
        self._pf = None
        self._pf_epoch = -1

    # ------------------------------------------------------------------
    # fleet init: slab-wise stacked init → host store + server state
    # ------------------------------------------------------------------
    def _ready(self):
        """Initialise the fleet on first use (`carry_template`, `run_chunk`
        or `restore`): the serve loop builds the engine before its
        ``serve.warm`` span and warms it inside, as it does the stacked
        engine's init."""
        if self._names is None:
            self._init_fleet()

    def _make_basis(self, n: int):
        return (None if self._basis_kind is None
                else standard_basisb(self.d, n))

    def _init_fleet(self):
        spec, store, x0 = self.spec, self.store, self.x0
        n = self.n
        names = tuple(getattr(spec, "carry_names", ()))
        slabs = [(lo, min(lo + self.slab, n)) for lo in range(0, n, self.slab)]
        state: dict = {}
        extras_sums: dict = {}
        env_last = None
        carry_last = None
        for lo, hi in slabs:
            sn = hi - lo
            batch = store.gather_batch(np.arange(lo, hi))
            basisb = self._make_basis(sn)
            R = rounds.VmapReducer(n=sn)
            # the SAME cached program the stacked serve path inits with —
            # at one slab the carry is bitwise the stacked engine's carry;
            # `serve_init` also shares the stacked path's AOT cache entries
            # when a program cache is active
            carry = rounds.serve_init(spec, R, batch, basisb, x0)
            if self._is_client is None:
                self._split_carry_contract(spec, names, carry, batch,
                                           basisb, x0)
            for name, elem, cl in zip(names, carry, self._is_client):
                if cl:
                    arr = np.asarray(elem)
                    if name not in state:
                        state[name] = np.empty((n,) + arr.shape[1:],
                                               arr.dtype)
                    state[name][lo:hi] = arr
                elif lo == 0:
                    self._server[name] = elem
            if len(slabs) > 1:
                ex = _slab_extras(spec, R, batch, basisb, x0, carry)
                for ename, ev in ex.items():
                    s = np.sum(np.asarray(ev, np.float64), axis=0)
                    extras_sums[ename] = (s if ename not in extras_sums
                                          else extras_sums[ename] + s)
                if hi == n:
                    env_last = rounds.Env(
                        batch=batch, basisb=basisb, x0=x0,
                        extra=spec.prepare(R, batch, basisb, x0))
                    carry_last = carry
        store.state = state
        if len(slabs) > 1:
            # server elements derived from a FLEET reduction (e.g. BAG's
            # H⁰ = meanᵢ recon(L⁰ᵢ) + ridge) must come from the accumulated
            # cross-slab sums, not from any single slab's init
            over = spec.cohort_server_init(
                env_last, {k: jnp.asarray(v) for k, v in extras_sums.items()},
                n, carry_last)
            for name, val in over.items():
                self._server[name] = jnp.asarray(val)
        # totals and frozen stats stay arrays (0-d, not numpy scalars, for a
        # per-client scalar leaf), so that `checkpoint_view` can borrow them
        for agg, (leaf, op) in self._aggs.items():
            if op == "mean":
                self._totals[agg] = np.asarray(np.sum(
                    state[leaf].astype(np.float64), axis=0))

    def _split_carry_contract(self, spec, names, carry, batch, basisb, x0):
        if not isinstance(carry, tuple) or len(names) != len(carry):
            raise ValueError(
                f"{type(spec).__name__}.carry_names has {len(names)} names "
                f"but init returns {len(carry) if isinstance(carry, tuple) else type(carry)} "
                "elements — the streaming engine needs one name per "
                "top-level carry element")
        flags = rounds.carry_client_flags(spec, batch, basisb, x0)
        is_client = []
        for name, fl, elem in zip(names, flags, carry):
            leaves = jax.tree_util.tree_leaves(fl)
            if any(leaves) and not all(leaves):
                raise ValueError(
                    f"carry element {name!r} mixes client-stacked and "
                    "server leaves — not streamable")
            cl = bool(leaves and all(leaves))
            if cl and len(jax.tree_util.tree_leaves(elem)) != 1:
                raise ValueError(
                    f"client-stacked carry element {name!r} must be a "
                    "single array to live in the ClientStore")
            is_client.append(cl)
        self._is_client = tuple(is_client)
        self._treedef = jax.tree_util.tree_structure(carry)
        for agg, (leaf, _op) in self._aggs.items():
            if leaf not in names or not is_client[names.index(leaf)]:
                raise ValueError(
                    f"cohort aggregate {agg!r} references carry leaf "
                    f"{leaf!r}, which is not a client-stacked element")
        self._names = names

    # ------------------------------------------------------------------
    # cohort sampling: counter-based, chunk-boundary invariant
    # ------------------------------------------------------------------
    def _sampler_seed(self) -> int:
        k = jax.random.fold_in(self.root_key, COHORT_SALT)
        try:
            if jnp.issubdtype(k.dtype, jax.dtypes.prng_key):
                k = jax.random.key_data(k)
        except (AttributeError, TypeError):
            pass
        kd = np.asarray(k).astype(np.uint64).ravel()
        seed = int(kd[0])
        if kd.size > 1:
            seed = (seed << 32) | int(kd[1])
        return seed

    def cohort_indices(self, epoch: int) -> np.ndarray:
        """Epoch's sorted cohort (unique global indices) — a pure function
        of (root key, epoch): Philox keyed by ``(seed64 << 64) + epoch``,
        so the schedule never depends on chunking or on trajectory state."""
        n, c = self.n, self.cohort
        rng = np.random.Generator(
            np.random.Philox(key=(self._seed64 << 64) + int(epoch)))
        if c * 8 <= n:
            # rejection path: first c distinct values in draw order (an
            # unbiased without-replacement sample at O(c) draws)
            chosen = np.empty(0, np.int64)
            while chosen.size < c:
                cand = rng.integers(0, n, size=2 * c, dtype=np.int64)
                merged = np.concatenate([chosen, cand])
                _uniq, first = np.unique(merged, return_index=True)
                chosen = merged[np.sort(first)]
            idx = chosen[:c]
        else:
            idx = rng.permutation(n)[:c]
        return np.sort(idx).astype(np.int64)

    def _padded(self, idx: np.ndarray):
        pidx = np.zeros(self.cap, np.int64)
        pidx[:idx.size] = idx
        real = np.zeros(self.cap, bool)
        real[:idx.size] = True
        return pidx, real

    # ------------------------------------------------------------------
    # prefetch: next epoch's gather + H2D behind the current chunk's scan
    # ------------------------------------------------------------------
    def _prefetch_submit(self, epoch: int):
        if not self._prefetch_on or self._pf_epoch == epoch:
            return

        def work():
            with spans.span("cohort.gather", epoch=epoch) as sp:
                idx = self.cohort_indices(epoch)
                pidx, real = self._padded(idx)
                A, b = self.store.gather_data(pidx)
                if not self.sharded:
                    # vmap backend: commit the H2D transfer on this thread
                    # too; the sharded backend re-lays arrays across the
                    # mesh at dispatch, so only the host gather is hoisted
                    A, b = jnp.asarray(A), jnp.asarray(b)
            return idx, pidx, real, A, b, sp.elapsed_s

        self._pf_epoch = epoch
        self._pf = self._pool.submit(work)

    def _fetch_epoch(self, epoch: int):
        if self._pf is not None and self._pf_epoch == epoch:
            with spans.span("cohort.prefetch_wait") as sp:
                idx, pidx, real, A, b, work_s = self._pf.result()
            self._pf = None
            self.metrics["prefetch_wait_us"] += sp.elapsed_s * 1e6
            self.metrics["prefetch_work_us"] += work_s * 1e6
            self.metrics["epochs_prefetched"] += 1
            return idx, pidx, real, A, b
        idx = self.cohort_indices(epoch)
        pidx, real = self._padded(idx)
        A, b = self.store.gather_data(pidx)
        return idx, pidx, real, A, b

    @property
    def prefetch_overlap(self) -> float:
        """Fraction of prefetch work hidden behind compute: 1 − wait/work
        over the prefetched epochs (1.0 = fully overlapped)."""
        work = self.metrics["prefetch_work_us"]
        if work <= 0.0:
            return 0.0
        return max(0.0, 1.0 - self.metrics["prefetch_wait_us"] / work)

    # ------------------------------------------------------------------
    # epoch residency
    # ------------------------------------------------------------------
    def _load_epoch(self, epoch: int):
        idx, pidx, real, A, b = self._fetch_epoch(epoch)
        self.metrics["h2d_bytes"] += int(A.nbytes) + int(b.nbytes)
        self.metrics["epochs_loaded"] += 1
        batch = client_batch.ClientBatch(A=jnp.asarray(A), b=jnp.asarray(b),
                                         lam=self.store.lam)
        elems = []
        for name, cl in zip(self._names, self._is_client):
            elems.append(jnp.asarray(self.store.state[name][pidx]) if cl
                         else self._server[name])
        frozen_np = {}
        for agg, (leaf, op) in self._aggs.items():
            rows = self.store.state[leaf][idx].astype(np.float64)
            if op == "mean":
                frozen_np[agg] = np.asarray(self._totals[agg]
                                            - rows.sum(axis=0))
            else:  # max over the ABSENT clients (streaming ⇒ some exist)
                mask = np.ones(self.n, bool)
                mask[idx] = False
                frozen_np[agg] = np.asarray(np.max(
                    self.store.state[leaf][mask].astype(np.float64), axis=0))
        self._cur = {
            "epoch": int(epoch), "idx": idx,
            "cidx": jnp.asarray(pidx, jnp.int32),
            "real": jnp.asarray(real),
            "batch": batch, "carry": tuple(elems),
            "frozen": {k: jnp.asarray(v) for k, v in frozen_np.items()},
            "frozen_np": frozen_np,
        }
        self._prefetch_submit(epoch + 1)

    def _unload_current(self):
        cur = self._cur
        if cur is None:
            return
        k = cur["idx"].size
        new_rows = {}
        for name, elem, cl in zip(self._names, cur["carry"],
                                  self._is_client):
            if cl:
                rows = np.asarray(elem)[:k]
                self.store.state[name][cur["idx"]] = rows
                new_rows[name] = rows
            else:
                self._server[name] = elem
        for agg, (leaf, op) in self._aggs.items():
            if op == "mean":
                # totals = frozen (absent, unchanged) + updated cohort rows
                self._totals[agg] = np.asarray(
                    cur["frozen_np"][agg]
                    + new_rows[leaf].astype(np.float64).sum(axis=0))
        self._cur = None

    # ------------------------------------------------------------------
    # program warming (repro.core.progcache)
    # ------------------------------------------------------------------
    def warm_programs(self, chunk: int) -> bool:
        """Resolve this engine's chunk program — load from the active
        program cache or compile-and-persist — without running a round or
        touching engine state.  All arguments are zero-valued templates at
        dispatch shapes (the store's dtypes, the padded capacity, the
        epoch-aligned first-segment length), so the serve loop can warm
        BEFORE checkpoint restore.  Returns False when no cache is
        active."""
        if rounds.progcache.active() is None:
            return False
        carry = self.carry_template()
        batch = client_batch.ClientBatch(
            A=jnp.zeros((self.cap,) + self.store.A.shape[1:],
                        self.store.A.dtype),
            b=jnp.zeros((self.cap,) + self.store.b.shape[1:],
                        self.store.b.dtype),
            lam=self.store.lam)
        # frozen templates mirror `_load_epoch`'s jnp.asarray(float64)
        # conversion so the warm signature matches the dispatch signature
        frozen = {}
        for agg, (leaf, op) in self._aggs.items():
            shape = (self._totals[agg].shape if op == "mean"
                     else self.store.state[leaf].shape[1:])
            frozen[agg] = jnp.asarray(np.zeros(shape, np.float64))
        # run_chunk cuts segments at epoch boundaries, so the first (and
        # dominant) segment length is min(chunk, rounds_per_cohort)
        prog, args = rounds.chunk_program(
            self.spec, batch, self._basis_cap, self.x0, carry,
            0, min(int(chunk), self.rpc), self.root_key,
            cohort=(np.zeros(self.cap, np.int32), np.ones(self.cap, bool),
                    frozen, self.n),
            sharded=self.sharded)
        prog.resolve(*args)
        return True

    # ------------------------------------------------------------------
    # driver
    # ------------------------------------------------------------------
    def run_chunk(self, t0: int, steps: int, avail=None):
        """Run rounds [t0, t0+steps) and return the history streams
        ``(eval_x, CommLedger-of-streams, events)`` — the same tuple as
        `rounds.run_chunk`.  Segments are cut at epoch boundaries
        internally; any chunking of calls produces the same streams
        (chunk-boundary invariance, pinned by tests).  ``avail`` must be
        None: client absence is the engine's own participation draw."""
        if avail is not None:
            raise ValueError(
                "cohort streaming does not take an availability schedule "
                "— fault plans address the stacked fleet by index")
        self._ready()
        outs = []
        t = int(t0)
        end = t + int(steps)
        while t < end:
            e = t // self.rpc
            if self._cur is None or self._cur["epoch"] != e:
                with spans.span("cohort.unload"):
                    self._unload_current()
                with spans.span("cohort.load", epoch=e):
                    self._load_epoch(e)
            cur = self._cur
            seg = min(end, (e + 1) * self.rpc) - t
            prog, args = rounds.chunk_program(
                self.spec, cur["batch"], self._basis_cap, self.x0,
                cur["carry"], t, seg, self.root_key,
                cohort=(cur["cidx"], cur["real"], cur["frozen"], self.n),
                sharded=self.sharded)
            cur["carry"], ys = prog(*args)
            outs.append(ys)
            t += seg
        if len(outs) == 1:
            return outs[0]
        return jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *outs)

    # ------------------------------------------------------------------
    # checkpoint plumbing (repro.exp/ckpt@2)
    # ------------------------------------------------------------------
    def carry_template(self):
        """Shape/dtype template of the device carry (the serialization
        contract the serve loop validates checkpoints against).  The first
        call initialises the fleet."""
        self._ready()
        elems = []
        for name, cl in zip(self._names, self._is_client):
            if cl:
                st = self.store.state[name]
                elems.append(jnp.zeros((self.cap,) + st.shape[1:], st.dtype))
            else:
                elems.append(self._server[name])
        return tuple(elems)

    def checkpoint_payload(self):
        """(carry_leaves, host_state) for `artifacts.save_checkpoint`, as
        copies the caller may hold while the engine runs on.

        The store rows of the CURRENT cohort are its epoch-start values
        (scatter-back is lazy), the device carry holds their live values,
        and ``frozen`` is the epoch's frozen fleet contribution — together
        exactly the state `restore` needs for a bit-exact mid-epoch resume.
        Copies, not views: the store rows mutate in place at the next epoch
        unload, so a view would change under a payload held past the next
        `run_chunk`."""
        leaves, host = self.checkpoint_view()
        return leaves, {k: np.array(v) for k, v in host.items()}

    def checkpoint_view(self):
        """`checkpoint_payload` without the copy of the host plane, for a
        caller that consumes it before the engine runs on (the serve loop
        writes its checkpoint before its next chunk).

        ``host_state`` holds views (`np.ndarray.view`) of the store, the
        fleet totals and the frozen epoch stats, valid until the next
        `run_chunk` or `restore`: the unload that the next chunk may start
        writes the cohort's rows into the store, and `restore` replaces it.
        The carry leaves are host copies of the device carry either way."""
        if self._cur is None:
            raise RuntimeError("no rounds have run — nothing to checkpoint")
        leaves = [np.array(l)
                  for l in jax.tree_util.tree_leaves(self._cur["carry"])]
        host = {f"store/{k}": v for k, v in self.store.state.items()}
        host.update({f"totals/{k}": v for k, v in self._totals.items()})
        host.update({f"frozen/{k}": v
                     for k, v in self._cur["frozen_np"].items()})
        return leaves, {k: v.view() for k, v in host.items()}

    def restore(self, t: int, carry, host_state: Optional[dict]):
        """Adopt a checkpoint taken at round ``t`` (``carry`` already
        validated/unflattened by the caller).  The resident epoch is
        ``(t−1) // rpc`` — the epoch of the last computed round; its cohort
        resamples deterministically and its data re-gathers from the store."""
        self._ready()
        host_state = host_state or {}
        frozen_np = {}
        for key, val in host_state.items():
            if key.startswith("store/"):
                self.store.state[key[len("store/"):]] = np.array(val)
            elif key.startswith("totals/"):
                self._totals[key[len("totals/"):]] = np.array(val, np.float64)
            elif key.startswith("frozen/"):
                frozen_np[key[len("frozen/"):]] = np.array(val, np.float64)
        missing = ({f"frozen/{a}" for a in self._aggs}
                   - {k for k in host_state if k.startswith("frozen/")})
        if missing:
            raise ValueError(
                f"checkpoint host_state lacks {sorted(missing)} — not a "
                "cohort-streaming ckpt@2 checkpoint for this spec")
        e = (int(t) - 1) // self.rpc
        idx = self.cohort_indices(e)
        pidx, real = self._padded(idx)
        A, b = self.store.gather_data(pidx)
        batch = client_batch.ClientBatch(A=jnp.asarray(A), b=jnp.asarray(b),
                                         lam=self.store.lam)
        self._cur = {
            "epoch": e, "idx": idx,
            "cidx": jnp.asarray(pidx, jnp.int32),
            "real": jnp.asarray(real),
            "batch": batch, "carry": tuple(carry),
            "frozen": {k: jnp.asarray(v) for k, v in frozen_np.items()},
            "frozen_np": frozen_np,
        }
        self._prefetch_submit(e + 1)

    def serve_meta(self) -> dict:
        """The serve record's ``meta`` keys of this engine: the cohort
        geometry and the prefetch counters."""
        return {"cohort": self.cohort, "rounds_per_cohort": self.rpc,
                "n_clients": self.n,
                "prefetch_overlap": self.prefetch_overlap,
                "prefetch": dict(self.metrics)}

    def close(self):
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None
