"""The sweep engine: execute registered experiments, cell by cell.

`build_problem` materializes a `ProblemSpec` once (clients, x0, reference
optimum x*, memoized basis fleets); `run_cell` dispatches one `MethodCell`
to the public method entry points (`repro.core.bl`, `repro.core.baselines`)
— every fast-path cell therefore runs on the unified jitted round engine
(`repro.core.rounds`), on whichever aggregation backend the cell declares
(``backend="fast+sharded"`` shards clients over the mesh).  `run_experiment`
sweeps (cell × seed), skips cells whose artifact already exists with a
matching config digest (resume), and regenerates the figure CSVs from the
artifacts — so CSVs are always consistent with the JSON records.

Long cells can stream progress mid-sweep: ``progress_every=N`` attaches a
`repro.core.rounds.StreamHook` that reports (round, gap, Mbits/node) at
chunk boundaries for the BL methods — on the single-device AND sharded
backends alike (the driver chunks the scan to the hook's cadence).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines, batched, bl, client_batch, compressors, glm
from repro.core.basis import make_bases
from repro.core.rounds import StreamHook

from . import artifacts
from .registry import (
    CompressorCfg,
    DNNProblemSpec,
    Experiment,
    LMFinetuneSpec,
    MethodCell,
    ProblemSpec,
)


def build_compressor(cfg: CompressorCfg, d: int) -> compressors.Compressor:
    """Materialize a declarative `CompressorCfg` for a d-dimensional problem
    (the composed Rank-R codecs derive their dithering levels from d)."""
    k = cfg.kind
    if k == "identity":
        return compressors.Identity()
    if k == "topk":
        return compressors.TopK(k=cfg.k, symmetrize=cfg.symmetrize)
    if k == "randk":
        return compressors.RandK(k=cfg.k)
    if k == "rankr":
        return compressors.RankR(r=cfg.r)
    if k == "dither":
        return compressors.RandomDithering(s=cfg.s)
    if k == "natural":
        return compressors.NaturalCompression()
    if k == "rtopk":
        return compressors.rtopk(cfg.k)
    if k == "ntopk":
        return compressors.ntopk(cfg.k)
    if k == "rrankr":
        return compressors.rrankr(cfg.r, d)
    if k == "nrankr":
        return compressors.nrankr(cfg.r)
    if k == "bernoulli":
        return compressors.BernoulliLazy(p=cfg.p)
    raise ValueError(f"unknown compressor kind {cfg.kind!r}")


@dataclasses.dataclass
class Problem:
    """A built problem regime: data, initial iterate, reference optimum."""

    spec: ProblemSpec
    clients: list
    x0: jax.Array
    x_star: jax.Array
    _bases: Dict[str, list] = dataclasses.field(default_factory=dict)

    @property
    def d(self) -> int:
        return int(self.x0.shape[0])

    @property
    def n(self) -> int:
        return len(self.clients)

    def bases(self, name: str) -> list:
        """Per-client basis fleet for a `repro.core.basis` registry name,
        built once per problem and memoized across cells."""
        if name not in self._bases:
            self._bases[name] = make_bases(name, self.clients, x0=self.x0)
        return self._bases[name]


@dataclasses.dataclass
class DNNProblem:
    """A built `DNNProblemSpec`: client-stacked data, student init, and the
    (stable, memoized) loss/eval closures — stable function identities keep
    the engine's jit cache warm across cells and seeds."""

    spec: DNNProblemSpec
    batch: object                    # client_batch.TreeBatch
    params0: object                  # parameter pytree
    loss_fn: object
    eval_fn: object
    #: fine-tuning (`LMFinetuneSpec`): the frozen trunk and the federated
    #: leaves' matrix views for the per-layer SVD basis
    trunk: object = None
    views: Optional[tuple] = None

    @property
    def n(self) -> int:
        return self.batch.n

    @property
    def x0(self):
        """The engine's round-0 input: the params, with the trunk beside
        them when it is frozen (`specs.BLDNNSpec.frozen`)."""
        return self.params0 if self.trunk is None else (self.params0, self.trunk)


@dataclasses.dataclass
class StreamProblem:
    """A built kind="synthetic_stream" regime: the fleet lives in a
    host-resident `client_batch.ClientStore` (never stacked on device) and
    the reference optimum comes from the slab-wise host Newton solver —
    the problem form the cohort-streaming engine (`repro.core.cohort`)
    consumes.  ≥100k clients fit where a stacked `Problem` would not."""

    spec: ProblemSpec
    store: object                    # client_batch.ClientStore
    x0: jax.Array
    x_star: np.ndarray

    @property
    def d(self) -> int:
        return int(self.x0.shape[0])

    @property
    def n(self) -> int:
        return self.store.n


@functools.lru_cache(maxsize=None)
def build_problem(spec: ProblemSpec) -> Problem:
    """Materialize a `ProblemSpec` or `DNNProblemSpec` (memoized — figures
    share regimes)."""
    if isinstance(spec, LMFinetuneSpec):
        from repro.fed import bldnn

        cfg, batch, params0, trunk = bldnn.make_lm_finetune(
            seed=spec.seed, model=spec.model, n_layers=spec.n_layers,
            n_held=spec.n_held, first_held=spec.first_held, vocab=spec.vocab,
            n_clients=spec.n_clients, seq=spec.seq, zipf=spec.zipf,
            overrides=spec.overrides)
        return DNNProblem(spec=spec, batch=batch, params0=params0,
                          loss_fn=bldnn.make_lm_loss_fn(cfg), eval_fn=None,
                          trunk=trunk,
                          views=tuple(bldnn.ATTN_VIEWS[k] for k in sorted(params0)))
    if isinstance(spec, DNNProblemSpec):
        from repro.fed import bldnn

        batch, params0 = bldnn.make_synthetic_classification(
            seed=spec.seed, n_clients=spec.n_clients, m=spec.m, d=spec.d,
            classes=spec.classes, width=spec.width, r=spec.r,
            heterogeneity=spec.heterogeneity, label_noise=spec.label_noise)
        return DNNProblem(spec=spec, batch=batch, params0=params0,
                          loss_fn=bldnn.make_loss_fn(spec.classes),
                          eval_fn=bldnn.make_eval_fn())
    if spec.kind == "synthetic_stream":
        from repro.core import cohort

        store = client_batch.synthetic_store(
            spec.seed, spec.n_clients, spec.m, spec.d, lam=spec.lam)
        x0 = jnp.zeros(spec.d, jnp.float64)
        x_star = cohort.store_newton_solve(store, np.zeros(spec.d),
                                           iters=spec.newton_iters)
        return StreamProblem(spec=spec, store=store, x0=x0, x_star=x_star)
    if spec.kind == "table2":
        clients = glm.make_table2(spec.name, seed=spec.seed, lam=spec.lam)
    elif spec.kind == "synthetic":
        clients = glm.make_synthetic(
            seed=spec.seed, n_clients=spec.n_clients, m=spec.m, d=spec.d,
            r=spec.r, lam=spec.lam)
    else:
        raise ValueError(f"unknown problem kind {spec.kind!r}")
    d = int(clients[0].A.shape[1])
    x0 = jnp.zeros(d, jnp.float64)
    if spec.solver == "fused":
        batch = client_batch.from_clients(clients)
        x_star = client_batch.newton_solve_fused(batch, x0, spec.newton_iters)
    elif spec.solver == "loop":
        x_star = glm.newton_solve(clients, x0, spec.newton_iters)
    else:
        raise ValueError(f"unknown solver {spec.solver!r}")
    return Problem(spec=spec, clients=clients, x0=x0, x_star=x_star)


#: methods that accept a PRNG seed (the sweep seed is injected only here;
#: newton/gd/local_gd are deterministic and take none)
_SEEDED_METHODS = frozenset(
    {"bl1", "bl2", "bl3", "fednl_bag", "nl1", "diana", "adiana", "dore",
     "bldnn"})


def _comp(cfg: Optional[CompressorCfg], d: int, what: str):
    if cfg is None:
        raise ValueError(f"cell needs a {what} compressor config")
    return build_compressor(cfg, d)


def build_stream_spec(cell: MethodCell, d: int, n: int, lam: float,
                      params: dict):
    """`MethodSpec` + basis kind for a store-backed streaming cell, built
    directly from the cell config (the stacked setups in
    `repro.core.batched` start from per-client lists, which a streaming
    fleet never materializes).  Field values mirror bl2_setup / bl3_setup /
    fednl_bag_setup exactly — same defaults, same ledger bit accounting.
    Pops the engine-level params (cohort, rounds_per_cohort, seed) from
    ``params`` and returns ``(spec, basis, cohort, rounds_per_cohort,
    seed)``."""
    from repro.core import cohort, specs

    m = cell.method
    cohort_size = int(params.pop("cohort", n))
    rpc = int(params.pop("rounds_per_cohort", 1))
    seed = int(params.pop("seed", 0))
    hc = _comp(cell.hess_comp, d, "hessian")
    if m == "bl2":
        mc = _comp(cell.model_comp, d, "model")
        bb = cohort.standard_basisb(d, n)
        init_exact = bool(params.pop("init_exact_hessian", True))
        spec = specs.BL2Spec(
            hess_comp=hc, model_comp=mc,
            alpha=params.pop("alpha", 1.0), eta=params.pop("eta", 1.0),
            p=params.pop("p", 1.0), tau=int(params.pop("tau", n)),
            init_exact=init_exact,
            init_hess_bits=bb.init_coeff_bits_mean(init_exact),
            basis_bits=bb.transmission_bits_mean(), block=False)
        basis = "standard"
    elif m == "bl3":
        mc = _comp(cell.model_comp, d, "model")
        spec = specs.BL3Spec(
            hess_comp=hc, model_comp=mc,
            alpha=params.pop("alpha", 1.0), eta=params.pop("eta", 1.0),
            p=params.pop("p", 1.0), tau=int(params.pop("tau", n)),
            c=params.pop("c", 1e-8), option=int(params.pop("option", 2)))
        basis = None
    elif m == "fednl_bag":
        bb = cohort.standard_basisb(d, n)
        init_exact = bool(params.pop("init_exact_hessian", True))
        q = params.pop("q", 0.5)
        eta = params.pop("eta", None)
        mu = params.pop("mu", None)
        spec = specs.FedNLBAGSpec(
            hess_comp=hc, alpha=params.pop("alpha", 1.0), q=q,
            eta=q if eta is None else eta, mu=lam if mu is None else mu,
            init_exact=init_exact,
            init_hess_bits=bb.init_coeff_bits_mean(init_exact),
            basis_bits=bb.transmission_bits_mean(), block=False)
        basis = "standard"
    else:
        raise ValueError(
            f"method {m!r} has no cohort-streaming path (bl2, bl3 and "
            "fednl_bag stream — see MethodSpec.supports_cohort)")
    if params:
        raise ValueError(
            f"unused streaming cell params {sorted(params)} for {m!r}")
    return spec, basis, cohort_size, rpc, seed


#: methods the serve loop can drive: the GLM specs, and bldnn on a
#: frozen-trunk fine-tuning problem (whose per-round evaluation is a dict
#: of scalars; a pytree iterate per round would not fit the streams)
SERVE_METHODS = ("bl1", "bl2", "bl3", "fednl_bag", "bldnn")


def build_setup(exp, cell, prob):
    """(spec, batch, basisb) for a registered cell on a stacked problem —
    the static half of a run, shared between the batch engine and the
    serve loop (the `repro.core.batched` ``*_setup`` factorization)."""
    m = cell.method
    if m not in SERVE_METHODS:
        raise SystemExit(
            f"fed_serve drives methods {', '.join(SERVE_METHODS)}; cell "
            f"{cell.name!r} uses {m!r} (run it via `python -m repro.exp`)")
    if m == "bldnn":
        from repro.fed import bldnn

        return bldnn.serve_setup(cell, prob)
    params = cell.params_dict()
    params.pop("seed", None)        # the serve PRNG root comes from --seed
    n, d = prob.n, prob.d
    clients = prob.clients
    hc = [_comp(cell.hess_comp, d, "hessian")] * n
    if m == "bl1":
        mc = _comp(cell.model_comp, d, "model")
        return batched.bl1_setup(clients, prob.bases(cell.basis), hc, mc,
                                 **params)
    if m == "bl2":
        mc = [_comp(cell.model_comp, d, "model")] * n
        return batched.bl2_setup(clients, prob.bases(cell.basis), hc, mc,
                                 **params)
    if m == "bl3":
        mc = [_comp(cell.model_comp, d, "model")] * n
        return batched.bl3_setup(clients, hc, mc, **params)
    return batched.fednl_bag_setup(clients, prob.bases(cell.basis), hc,
                                   **params)


def make_engine(exp, cell, prob, *, root_key, sharded: bool):
    """The engine that runs ``cell`` on ``prob`` — the one place that
    chooses it.  A store-backed problem (`StreamProblem`) whose cohort is
    smaller than its fleet streams through `cohort.CohortEngine`; anything
    else runs on `rounds.StackedEngine`, a store-backed fleet that one
    cohort covers gathered onto the device whole.  Both engines build
    nothing on the device here: their first ``carry_template`` does."""
    from repro.core import cohort, rounds

    if not isinstance(prob, StreamProblem):
        spec, batch, basisb = build_setup(exp, cell, prob)
        return rounds.StackedEngine(spec, batch, basisb, prob.x0, root_key,
                                    sharded=sharded)
    spec, basis, csize, rpc, _ = build_stream_spec(
        cell, prob.d, prob.n, prob.store.lam, cell.params_dict())
    if csize < prob.n:
        return cohort.CohortEngine(
            spec, prob.store, prob.x0, cohort=csize, rounds_per_cohort=rpc,
            root_key=root_key, basis=basis, sharded=sharded)
    basisb = None if basis is None else cohort.standard_basisb(prob.d, prob.n)
    return rounds.StackedEngine(
        spec, prob.store.gather_batch(np.arange(prob.n)), basisb, prob.x0,
        root_key, sharded=sharded)


def gap_evaluator(prob, eng):
    """``evaluate(evaluated) -> {name: stream}`` (``"gap"`` always) for the
    trajectories ``eng`` runs on ``prob``; f* is computed at first use.
    Keyed on the problem, not on the engine: a store-backed fleet evaluates
    slab-wise on the host (`cohort.store_loss` — the device never holds
    more than a cohort), anything else through the spec's
    ``eval_streams`` on the engine's batch (a fine-tuning problem has no
    f*)."""
    if isinstance(prob, StreamProblem):
        from repro.core import cohort

        loss = functools.partial(cohort.store_loss, prob.store)
        f_star = functools.cache(lambda: loss(prob.x_star))
        return lambda xs: {"gap": np.array([loss(x) - f_star()
                                            for x in np.asarray(xs)])}
    f_star = functools.cache(
        lambda: 0.0 if isinstance(prob, DNNProblem)
        else batched._f_star(eng.batch, prob.x_star))
    return lambda evaluated: eng.spec.eval_streams(eng.batch, evaluated,
                                                   f_star())


def _run_stream_cell(exp: Experiment, cell: MethodCell, prob: StreamProblem,
                     steps: int, params: dict, backend: str) -> bl.History:
    eng = make_engine(exp, cell, prob,
                      root_key=jax.random.PRNGKey(int(params.get("seed", 0))),
                      sharded=backend.endswith("+sharded"))
    try:
        eval_x, leds, _events = eng.run_chunk(0, steps)
    finally:
        eng.close()
    return batched._history(gap_evaluator(prob, eng)(eval_x), leds)


def run_cell(exp: Experiment, cell: MethodCell, prob: Problem, *,
             steps: Optional[int] = None, seed: Optional[int] = None,
             backend: Optional[str] = None,
             stream: Optional[StreamHook] = None) -> bl.History:
    """Run one cell and return its `History`.

    Args:
      exp, cell: the registered experiment and one of its cells.
      prob: the built problem (`build_problem(exp.problem)`).
      steps: override the cell's round budget — shorter OR longer (the
        benchmark wrappers extend runs; `run_experiment` clamps via its
        own ``max_steps``).
      seed: sweep seed; a ``seed`` in ``cell.params`` takes precedence
        (cells that pin a seed reproduce one specific committed curve).
      backend: override the cell's engine backend.
      stream: optional mid-sweep progress hook (BL methods, any fast
        backend — see `repro.core.rounds.StreamHook`).
    """
    m = cell.method
    steps = cell.steps if steps is None else steps
    backend = cell.backend if backend is None else backend
    params = cell.params_dict()
    if seed is not None and m in _SEEDED_METHODS:
        params.setdefault("seed", seed)

    if isinstance(prob, StreamProblem):
        if backend == "auto":
            backend = "cohort"
        if backend not in ("cohort", "cohort+sharded"):
            raise ValueError(
                f"cell {cell.name!r}: a synthetic_stream problem runs on "
                f"the cohort backends, got backend={backend!r}")
        return _run_stream_cell(exp, cell, prob, steps, params, backend)

    if m == "bldnn":
        from repro.fed import bldnn

        if not isinstance(prob, DNNProblem):
            raise ValueError(
                f"cell {cell.name!r} needs a DNNProblemSpec problem")
        if prob.trunk is not None:
            raise ValueError(
                f"cell {cell.name!r} fine-tunes with a frozen trunk, which "
                "the serve loop runs: python -m repro.launch.fed_serve "
                f"--exp <experiment> --cell {cell.name}")
        if cell.hess_comp is None:
            raise ValueError("bldnn cells configure the (gradient+Fisher) "
                             "compressor via hess_comp")
        run_seed = params.pop("seed", 0)
        from repro.core.basis import is_pytree_basis

        if cell.basis is not None and not is_pytree_basis(cell.basis):
            raise ValueError(
                f"cell {cell.name!r}: bldnn needs a pytree basis "
                f"(per_layer_svd / dct_tree / hadamard_tree), got "
                f"{cell.basis!r}")
        cfg = bldnn.BLDNNConfig(compressor=cell.hess_comp.kind,
                                use_basis=cell.basis is not None,
                                basis_kind=cell.basis or "per_layer_svd",
                                **params)
        # "auto" on a DNN cell means the engine's single-device fast path
        eng_backend = "fast" if backend == "auto" else backend
        return bldnn.run_bldnn(prob.loss_fn, prob.eval_fn, prob.params0,
                               prob.batch, steps, cfg, seed=run_seed,
                               backend=eng_backend, stream=stream)

    n, d = prob.n, prob.d
    clients, x0, xs = prob.clients, prob.x0, prob.x_star

    if m in ("bl1", "bl2", "bl3", "fednl_bag"):
        hc = [_comp(cell.hess_comp, d, "hessian")] * n
        if m == "bl1":
            mc = _comp(cell.model_comp, d, "model")
            return bl.bl1(clients, prob.bases(cell.basis), hc, mc, x0, xs,
                          steps, backend=backend, stream=stream, **params)
        if m == "bl2":
            mc = [_comp(cell.model_comp, d, "model")] * n
            return bl.bl2(clients, prob.bases(cell.basis), hc, mc, x0, xs,
                          steps, backend=backend, stream=stream, **params)
        if m == "bl3":
            mc = [_comp(cell.model_comp, d, "model")] * n
            return bl.bl3(clients, hc, mc, x0, xs, steps, backend=backend,
                          stream=stream, **params)
        return baselines.fednl_bag(clients, prob.bases(cell.basis), hc, x0,
                                   xs, steps, backend=backend, **params)
    if m == "newton":
        bases = prob.bases(cell.basis) if cell.basis else None
        return baselines.newton(clients, x0, xs, steps, bases=bases,
                                backend=backend, **params)
    if m == "nl1":
        return baselines.nl1(clients, x0, xs, steps, **params)
    if m == "gd":
        return baselines.gd(clients, x0, xs, steps, backend=backend, **params)
    if m == "diana":
        comp = _comp(cell.hess_comp, d, "gradient")
        return baselines.diana(clients, x0, xs, steps, comp,
                               comp.omega_for(d), backend=backend, **params)
    if m == "adiana":
        comp = _comp(cell.hess_comp, d, "gradient")
        return baselines.adiana(clients, x0, xs, steps, comp,
                                comp.omega_for(d), **params)
    if m == "local_gd":
        return baselines.local_gd(clients, x0, xs, steps, **params)
    if m == "dore":
        up = _comp(cell.hess_comp, d, "uplink")
        down = _comp(cell.model_comp, d, "downlink")
        return baselines.dore_like(clients, x0, xs, steps, up, down, **params)
    raise ValueError(f"unknown method {m!r} in cell {cell.name!r}")


def _progress_hook(exp: Experiment, cell: MethodCell, prob: Problem,
                   every: int, log) -> StreamHook:
    # The hook body runs inside a jax.debug.callback while the engine's
    # scan is still executing — re-entering JAX from a host callback can
    # deadlock, so the gap is evaluated in pure numpy on host copies of
    # the fleet (jax.debug.callback delivers eval_x/ledger as numpy).
    A = np.stack([np.asarray(c.A) for c in prob.clients])   # (n, m, d)
    b = np.stack([np.asarray(c.b) for c in prob.clients])   # (n, m)
    lam = prob.clients[0].lam
    x_star = np.asarray(prob.x_star)

    def loss(x):
        z = (A @ x) * b
        return float(np.mean(np.logaddexp(0.0, -z))
                     + 0.5 * lam * np.dot(x, x))

    f_star = loss(x_star)

    def report(t, eval_x, ledger):
        gap = loss(np.asarray(eval_x)) - f_star
        mb = float(np.asarray(ledger.uplink)) / 1e6
        log(f"    [{exp.name}/{cell.name}] round {t}: gap={gap:.3e} "
            f"up={mb:.3f} Mbits/node")

    return StreamHook(every=every, callback=report)


def run_experiment(exp: Experiment, out_dir: str, artifacts_dir: str, *,
                   force: bool = False, max_steps: Optional[int] = None,
                   cells: Optional[Sequence[str]] = None,
                   seeds: Optional[Sequence[int]] = None,
                   progress_every: Optional[int] = None,
                   log=print) -> List[dict]:
    """Sweep an experiment: run (cell × seed), write artifacts + CSVs.

    Cells whose artifact JSON already exists with a matching config digest
    are *skipped* (status "cached") unless ``force`` — re-running a partial
    sweep is idempotent and completes only the missing cells.  Figure CSVs
    are regenerated from the artifacts every time (cheap, keeps them
    consistent).  Returns one summary dict per (cell, seed).
    """
    summaries = []
    sweep_seeds = tuple(seeds) if seeds is not None else exp.seeds
    run_cells = (exp.cells if cells is None
                 else tuple(exp.cell(c) for c in cells))
    prob = None
    for cell in run_cells:
        eff_steps = (cell.steps if max_steps is None
                     else min(cell.steps, max_steps))
        for seed in sweep_seeds:
            config = artifacts.cell_config(exp, cell, seed, eff_steps)
            digest = artifacts.config_digest(config)
            path = artifacts.artifact_path(artifacts_dir, exp.name,
                                           cell.name, seed)
            record = None if force else artifacts.load_json(path)
            if record is not None and record.get("config_digest") == digest:
                status = "cached"
            else:
                if prob is None:
                    prob = build_problem(exp.problem)
                stream = None
                if progress_every and cell.method in ("bl1", "bl2", "bl3"):
                    # chunk-boundary emission works on every fast backend,
                    # sharded included (see rounds.StreamHook)
                    stream = _progress_hook(exp, cell, prob,
                                            progress_every, log)
                t0 = time.perf_counter()
                hist = run_cell(exp, cell, prob, steps=eff_steps, seed=seed,
                                stream=stream)
                jax.effects_barrier()   # drain any stream-hook callbacks
                runtime = time.perf_counter() - t0
                record = artifacts.cell_record(exp, cell, seed, eff_steps,
                                               hist, runtime_s=runtime)
                artifacts.write_json(path, record)
                status = "ran"
            csv_file = None
            if seed == sweep_seeds[0]:
                csv_file = artifacts.write_fig_csv(out_dir, record)
            b2t = record["bits_to_tol"]
            summaries.append({
                "experiment": exp.name, "cell": cell.name, "seed": seed,
                "status": status, "steps": eff_steps,
                "mbits_to_tol": b2t["mbits_per_node"],
                "reached": b2t["reached"],
                "final_gap": record["history"]["gaps"][-1],
                "runtime_s": record.get("runtime_s"),
                "artifact": path, "csv": csv_file,
            })
            reach = (f"{b2t['mbits_per_node']:.3f} Mbits to {exp.tol:g}"
                     if b2t["reached"] else
                     f"tol not reached (gap {record['history']['gaps'][-1]:.2e})")
            log(f"  {exp.name}/{cell.name} seed={seed} [{status}] "
                f"{eff_steps} rounds — {reach}")
    return summaries
