"""Fault-tolerant federated service loop over the unified round engine.

    python -m repro.launch.fed_serve --exp fig4 --cell BL2_tau_half \
        --max-rounds 200 --chunk 25 --ckpt-dir runs/serve

Where `repro.exp` runs a cell as one fixed-length batch scan and exits, this
launcher *serves* it: rounds run in bounded-length chunks against the
chunked scan driver (`repro.core.rounds.run_chunk` — the jitted program is
reused across chunks, control returns to the host every chunk), and between
chunks the orchestrator

  1. **injects faults** — a `repro.core.faults.FaultPlan` (i.i.d. dropout,
     deterministic outage windows, straggler timeouts with retry/backoff)
     materializes the next chunk's availability schedule, which reaches the
     method spec as `RoundCtx.avail`.  When a round's surviving cohort falls
     below its τ target the engine degrades gracefully (force-one-client
     fallback) and the round is flagged in the events stream
     (`History.events`, `rounds.EVENT_*` bitmasks).
  2. **checkpoints** the full server state — scan carry (iterate, shifts,
     `comm.CommLedger`), accumulated history streams, root PRNG key and
     round counter — via `repro.exp.artifacts.save_checkpoint`
     (schema-versioned, atomically written, digest-keyed to this serve
     config).

Start-up is **compile-free on a warm restart**: the serve programs resolve
through the AOT program cache (`repro.core.progcache`, rooted at
``<ckpt_dir>/progcache`` by default, ``--progcache-dir``/``--no-progcache``
to move/disable) *before* checkpoint restore, so a restarted server
deserializes its executables in milliseconds instead of recompiling —
time-to-first-round and cache outcomes land in the record's ``meta``
(``ttfr_s``, ``progcache``).  From the command line every other compile
(problem construction, gap evaluation) goes through jax's persistent
compilation cache, which `progcache.enable_compile_cache` places where
``JAX_COMPILATION_CACHE_DIR`` says, or else in the checkout's
``.jax_cache``.  ``--metrics-out`` additionally streams an append-only, crash-safe JSONL line per round (round, gap, degradation
events, per-leg ledger bits — `MetricsSink`).

The record's ``meta`` is the operator's view of where a serve call spent
its time, with no profiler needed: ``meta.spans`` holds, per host span
(`repro.core.spans`), the count and total seconds of those that closed
during the call — ``serve.warm`` and ``serve.restore`` (start-up, together
``ttfr_s`` less the first chunk), ``serve.dispatch`` (a chunk's dispatch;
on the cohort engine its epoch swaps too), ``serve.pull`` (the chunk's
outputs to the host, which waits for the device), ``serve.sink``
(``--metrics-out`` lines), ``serve.checkpoint`` and inside it
``ckpt.payload``, ``ckpt.write``, ``ckpt.digest``, ``ckpt.prune``, and the
cohort engine's ``cohort.unload``, ``cohort.load``,
``cohort.prefetch_wait``, ``cohort.gather``.  ``meta.retraces`` counts the
round programs traced during the call (`rounds.trace_counts`): a warm
restart traces none, so a non-empty entry there is a recompile.
``meta.server_eigh`` counts, of the rounds the call served, those whose
server step took the μ-projection's eigendecomposition
(`rounds.EVENT_EIGH`; `bl.proj_mu_route` skips it where a Cholesky factor
certifies that the projection does not bite); the record's
``degraded_rounds`` leaves that bit out.  Under a
profiler the same spans are annotations on the trace's host plane, on the
device events' clock.

Because per-round PRNG keys are ``fold_in(root_key, t)`` and every fault
draw is a pure function of ``(fault seed, t)``, the trajectory is invariant
to chunk boundaries: kill -9 the process at any point, rerun the same
command, and the run resumes from the latest valid checkpoint **bit-exactly
** — trajectory, `History.events` and per-leg `CommLedger` bit streams all
match an uninterrupted run at the same seed (pinned by tests/test_serve.py
and the CI ``serve-smoke`` job).  ``--crash-after-round N`` arms
`faults.CrashInjector` — a deterministic in-process SIGKILL after round N
is computed but before its covering checkpoint lands (omit the flag on
restart, or it crashes at the same boundary forever).

Supported methods: the GLM specs with client-stacked state (bl1, bl2, bl3,
fednl_bag).  Fault injection additionally requires the method to react to
availability (`MethodSpec.supports_faults`: bl2/bl3 partial participation,
fednl_bag lazy aggregation) — serving bl1 works, but injecting faults into
it is refused rather than silently ignored.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batched, comm, faults, progcache, rounds, spans
from repro.exp import artifacts
from repro.exp.engine import (
    StreamProblem,
    _comp,
    build_problem,
    build_stream_spec,
)
from repro.exp.registry import get_experiment

#: methods the serve loop can drive (GLM specs; the DNN spec's pytree
#: eval stream needs a different stream accumulator)
SERVE_METHODS = ("bl1", "bl2", "bl3", "fednl_bag")

#: checkpoint stream names: eval iterates, events, one per ledger leg
_STREAMS = ("eval_x", "events") + tuple(
    f"led_{leg}" for leg in comm.CommLedger.LEGS)


def build_setup(exp, cell, prob):
    """(spec, batch, basisb) for a registered cell — the static half of a
    run, shared between the batch engine and the serve loop (the
    `repro.core.batched` ``*_setup`` factorization)."""
    m = cell.method
    if m not in SERVE_METHODS:
        raise SystemExit(
            f"fed_serve drives methods {', '.join(SERVE_METHODS)}; cell "
            f"{cell.name!r} uses {m!r} (run it via `python -m repro.exp`)")
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


def serve_config(exp, cell, seed: int, backend: str,
                 plan: faults.FaultPlan) -> dict:
    """The serve run's identity record — digest-keyed checkpoints resume
    only runs with identical identity.  Deliberately excludes the chunk
    length and round budget: chunking does not change the trajectory (the
    fold_in key contract), and raising ``--max-rounds`` on a finished run
    *extends* it from its last checkpoint instead of restarting."""
    return {
        "schema": artifacts.SERVE_SCHEMA,
        "experiment": exp.name,
        "problem": dataclasses.asdict(exp.problem),
        "cell": dataclasses.asdict(cell),
        "seed": seed,
        "backend": backend,
        "faults": plan.describe(),
    }


def _resolve_backend(cell, override: Optional[str]) -> str:
    backend = override or cell.backend
    if backend == "auto":
        backend = "fast"
    if backend not in ("fast", "fast+sharded"):
        raise SystemExit(
            f"fed_serve runs on the engine backends 'fast' or "
            f"'fast+sharded', not {backend!r} (the reference backend has "
            "no checkpointable scan carry)")
    return backend


def _resolve_cohort_backend(cell, override: Optional[str]) -> str:
    backend = override or cell.backend
    if backend == "auto":
        backend = "cohort"
    if backend not in ("cohort", "cohort+sharded"):
        raise SystemExit(
            f"a synthetic_stream cell serves on the 'cohort' or "
            f"'cohort+sharded' backends, not {backend!r} (the stacked "
            "backends would materialize the whole fleet on device)")
    return backend


def _empty_streams(d: int) -> dict:
    z64 = lambda: np.zeros((0,), np.float64)
    return {"eval_x": np.zeros((0, d), np.float64),
            "events": np.zeros((0,), np.int32),
            **{f"led_{leg}": z64() for leg in comm.CommLedger.LEGS}}


def _append_chunk(streams: dict, ys) -> dict:
    """The chunk's output streams appended on the host (the ``serve.pull``
    span: it waits for the chunk's device work)."""
    xs, leds, evs = ys
    cat = lambda name, arr: np.concatenate(
        [streams[name], np.asarray(arr)], axis=0)
    with spans.span("serve.pull"):
        out = {"eval_x": cat("eval_x", xs), "events": cat("events", evs)}
        for leg in comm.CommLedger.LEGS:
            out[f"led_{leg}"] = cat(f"led_{leg}", getattr(leds, leg))
    return out


def _retraces(before: dict) -> dict:
    """{program kind: traces} since ``before`` (a `rounds.trace_counts`)."""
    return {kind: n - before.get(kind, 0)
            for kind, n in rounds.trace_counts().items()
            if n > before.get(kind, 0)}


def _restore_carry(ck: dict, template) -> object:
    """Checkpoint leaves → carry pytree, validated leaf-by-leaf against a
    fresh `init_serve_carry` shape evaluation (the serialization contract:
    a spec whose carry changed shape fails loudly, not bit-rottingly)."""
    leaves0, treedef = jax.tree_util.tree_flatten(template)
    got = ck["carry_leaves"]
    if len(got) != len(leaves0):
        raise SystemExit(
            f"checkpoint carry has {len(got)} leaves, this spec expects "
            f"{len(leaves0)} — the method's carry structure changed; "
            "delete the checkpoint directory to restart from round 0")
    for i, (g, w) in enumerate(zip(got, leaves0)):
        if tuple(g.shape) != tuple(w.shape) or g.dtype != np.asarray(w).dtype:
            raise SystemExit(
                f"checkpoint carry leaf {i} is {g.dtype}{tuple(g.shape)}, "
                f"spec expects {np.asarray(w).dtype}{tuple(np.asarray(w).shape)}"
                " — incompatible checkpoint; delete the checkpoint "
                "directory to restart from round 0")
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(g) for g in got])


class MetricsSink:
    """Append-only, crash-safe JSONL metrics stream for a serve run.

    One line per round: ``{"round", "gap", "events", "legs": {leg: bits}}``
    (cumulative per-leg `comm.CommLedger` bits, like the history record).
    Crash safety mirrors the checkpoint walk: on open, the existing file is
    scanned up to its last PARSEABLE line and emission resumes strictly
    after that round — a torn tail line from a killed process is simply
    overwritten territory (a lone "\\n" terminates it first), and re-served
    chunks after a resume never duplicate rounds.  Each chunk's lines are
    flushed and fsynced together, so the stream trails the trajectory by at
    most one chunk."""

    def __init__(self, path: str):
        self.path = path
        self.last_round = -1
        self._needs_newline = False
        if os.path.exists(path):
            with open(path, "rb") as f:
                raw = f.read()
            for line in raw.splitlines():
                try:
                    rec = json.loads(line)
                    self.last_round = max(self.last_round, int(rec["round"]))
                except (ValueError, KeyError, TypeError):
                    break               # torn tail — ignore it and beyond
            self._needs_newline = bool(raw) and not raw.endswith(b"\n")
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)

    def emit_chunk(self, ts, gaps, events, legs: dict) -> None:
        """Append rounds ``ts`` (parallel arrays); rounds at or below the
        resume point are skipped."""
        lines = []
        for i, t in enumerate(ts):
            t = int(t)
            if t <= self.last_round:
                continue
            lines.append(json.dumps({
                "round": t,
                "gap": float(gaps[i]),
                "events": int(events[i]),
                "legs": {leg: float(legs[leg][i]) for leg in legs},
            }))
            self.last_round = t
        if not lines:
            return
        with open(self.path, "a") as f:
            if self._needs_newline:
                f.write("\n")
                self._needs_newline = False
            f.write("\n".join(lines) + "\n")
            f.flush()
            os.fsync(f.fileno())

    def as_stream_hook(self, every: int, batch, f_star) -> rounds.StreamHook:
        """Adapter for the batch driver: a `rounds.StreamHook` whose
        emissions land in this sink (gap computed from the emitted
        iterate; chunk-boundary rounds only)."""
        def cb(t, eval_x, ledger):
            gap = rounds.default_gap_stream(
                batch, jnp.asarray(eval_x)[None, :], f_star)[0]
            self.emit_chunk([t], [float(gap)], [0],
                            {leg: [float(getattr(ledger, leg))]
                             for leg in comm.CommLedger.LEGS})
        return rounds.StreamHook(every=every, callback=cb)


def _degraded(events) -> int:
    """Rounds whose event bits mark a degradation (`rounds.EVENT_EIGH`
    marks a route, not one)."""
    return int(np.count_nonzero(np.asarray(events) & rounds.EVENT_DEGRADATION))


def _server_eigh(events, resumed_from: Optional[int]) -> dict:
    """Of the rounds this call served, how many took the server step's
    eigendecomposition (`bl.proj_mu_route`)."""
    served = np.asarray(events)[resumed_from or 0:]
    return {"rounds": int(np.count_nonzero(served & rounds.EVENT_EIGH)),
            "of": int(served.size)}


def _activate_progcache(ckpt_dir: str, progcache_dir: Optional[str],
                        no_progcache: bool, log):
    """Serve-loop AOT cache policy: ON by default, rooted next to the
    checkpoints (``<ckpt_dir>/progcache``) so a warm restart finds both."""
    if no_progcache:
        progcache.deactivate()
        return None
    cache = progcache.activate(progcache_dir
                               or os.path.join(ckpt_dir, "progcache"))
    log(f"[serve] program cache at {cache.root}")
    return cache


def _serve_cohort(exp, cell, prob: StreamProblem, *, seed: int, chunk: int,
                  max_rounds: int, ckpt_dir: str, backend: Optional[str],
                  keep: int, plan: Optional[faults.FaultPlan],
                  crash_after_round: Optional[int],
                  result_path: Optional[str],
                  progcache_dir: Optional[str] = None,
                  no_progcache: bool = False,
                  metrics_out: Optional[str] = None, log=print) -> dict:
    """The serve loop over the cohort-streaming engine: same chunked
    checkpoint/resume/crash contract as the stacked path, with the engine's
    host plane (client store, fleet totals, frozen epoch stats) riding in
    the ckpt@2 ``host_state`` payload.  The trajectory stays chunk-boundary
    invariant — per-round keys are ``fold_in(root_key, t)`` and the cohort
    schedule is a pure function of the absolute epoch index — so kill -9 +
    rerun is bit-exact here too (tests/test_cohort.py)."""
    from repro.core import cohort

    spans_before, traces_before = spans.snapshot(), rounds.trace_counts()
    plan = plan if plan is not None else faults.FaultPlan(n=prob.n)
    if not plan.trivial:
        raise SystemExit(
            "cohort streaming does not take an injected fault schedule: "
            "client absence is the engine's own per-round participation "
            "draw over the global fleet (Alg. 2-3 partial participation); "
            "drop the fault flags or serve a stacked cell")
    backend = _resolve_cohort_backend(cell, backend)
    crash = (faults.CrashInjector(crash_after_round)
             if crash_after_round is not None else None)
    params = cell.params_dict()
    params.pop("seed", None)        # the serve PRNG root comes from --seed
    spec, basis, csize, rpc, _ = build_stream_spec(
        cell, prob.d, prob.n, prob.store.lam, params)
    config = serve_config(exp, cell, seed, backend, plan)
    digest = artifacts.config_digest(config)
    root_key = jax.random.PRNGKey(seed)
    cache = _activate_progcache(ckpt_dir, progcache_dir, no_progcache, log)
    t0_wall = time.perf_counter()      # time-to-first-round starts here
    with spans.span("serve.warm"):
        eng = cohort.CohortEngine(
            spec, prob.store, prob.x0, cohort=csize, rounds_per_cohort=rpc,
            root_key=root_key, basis=basis,
            sharded=backend == "cohort+sharded")
        template = eng.carry_template()
        # resolve the chunk program BEFORE checkpoint restore: on a warm
        # restart the executable deserializes in milliseconds and the
        # first round starts compile-free
        eng.warm_programs(min(chunk, max_rounds))
    with spans.span("serve.restore"):
        ck = artifacts.load_checkpoint(ckpt_dir, config_digest=digest)
        resumed_from = None
        if ck is not None:
            t = int(ck["t"])
            carry = _restore_carry(ck, template)
            eng.restore(t, carry, ck.get("host_state"))
            streams = {name: np.asarray(ck["streams"][name])
                       for name in _STREAMS}
            resumed_from = t
            log(f"[serve] {exp.name}/{cell.name}: resumed from checkpoint "
                f"at round {t} (config {digest})")
        else:
            t = 0
            streams = _empty_streams(prob.d)
            log(f"[serve] {exp.name}/{cell.name}: fresh run (config "
                f"{digest}, cohort {eng.cohort}/{eng.n})")

    sink = MetricsSink(metrics_out) if metrics_out else None
    f_star = cohort.store_loss(prob.store, prob.x_star) if sink else None
    chunks_run = 0
    ttfr_s = None
    try:
        while t < max_rounds:
            steps = min(chunk, max_rounds - t)
            with spans.span("serve.dispatch", t=t, steps=steps):
                ys = eng.run_chunk(t, steps)
            streams = _append_chunk(streams, ys)
            t += steps
            chunks_run += 1
            if ttfr_s is None:
                ttfr_s = time.perf_counter() - t0_wall
            log(f"[serve] rounds {t - steps}..{t - 1} done "
                f"(epoch {(t - 1) // rpc})")
            if sink is not None:
                with spans.span("serve.sink"):
                    xs_new = np.asarray(streams["eval_x"][-steps:])
                    sink.emit_chunk(
                        range(t - steps, t),
                        [cohort.store_loss(prob.store, x) - f_star
                         for x in xs_new],
                        streams["events"][-steps:],
                        {leg: streams[f"led_{leg}"][-steps:]
                         for leg in comm.CommLedger.LEGS})
            if crash is not None:
                crash.maybe_crash(t - 1)
            with spans.span("serve.checkpoint"):
                with spans.span("ckpt.payload"):
                    leaves, host_state = eng.checkpoint_payload()
                artifacts.save_checkpoint(
                    ckpt_dir, t=t, carry_leaves=leaves, streams=streams,
                    root_key=np.asarray(root_key), config_digest=digest,
                    keep=keep, host_state=host_state)
    finally:
        eng.close()

    # fleet gaps evaluate slab-wise on the host (the device never holds
    # more than the cohort)
    xs = np.asarray(streams["eval_x"])
    f_star = (cohort.store_loss(prob.store, prob.x_star)
              if f_star is None else f_star)
    evals = {"gap": np.array([cohort.store_loss(prob.store, xs[i]) - f_star
                              for i in range(xs.shape[0])])}
    led_streams = comm.CommLedger(
        *(jnp.asarray(streams[f"led_{leg}"])
          for leg in comm.CommLedger.LEGS))
    hist = batched._history(evals, led_streams)
    hist.events = [int(e) for e in streams["events"]]
    record = {
        "schema": artifacts.SERVE_SCHEMA,
        "experiment": exp.name,
        "cell": cell.name,
        "seed": seed,
        "config_digest": digest,
        "config": config,
        "rounds": t,
        "history": {
            "gaps": [float(g) for g in hist.gaps],
            "up_bits": [float(b) for b in hist.up_bits],
            "down_bits": [float(b) for b in hist.down_bits],
            "legs": {leg: [float(v) for v in hist.legs[leg]]
                     for leg in comm.CommLedger.LEGS},
            "events": hist.events,
        },
        "degraded_rounds": _degraded(streams["events"]),
        "meta": {
            "backend": backend,
            "chunk": chunk,
            "chunks_run": chunks_run,
            "resumed_from": resumed_from,
            "straggler_wait_s": 0.0,
            "runtime_s": time.perf_counter() - t0_wall,
            "ttfr_s": ttfr_s,
            "progcache": cache.summary() if cache is not None else None,
            "cohort": eng.cohort,
            "rounds_per_cohort": rpc,
            "n_clients": eng.n,
            "prefetch_overlap": eng.prefetch_overlap,
            "prefetch": dict(eng.metrics),
            "server_eigh": _server_eigh(streams["events"], resumed_from),
            "spans": spans.since(spans_before),
            "retraces": _retraces(traces_before),
        },
    }
    if result_path:
        artifacts.write_json(result_path, record)
        log(f"[serve] result → {result_path}")
    log(f"[serve] {t} rounds, final gap {record['history']['gaps'][-1]:.3e}, "
        f"prefetch overlap {eng.prefetch_overlap:.0%}")
    return record


def serve(*, exp_name: str, cell_name: str, seed: int = 0, chunk: int = 25,
          max_rounds: int = 200, ckpt_dir: str, backend: Optional[str] = None,
          keep: int = 3, plan: Optional[faults.FaultPlan] = None,
          crash_after_round: Optional[int] = None,
          result_path: Optional[str] = None,
          progcache_dir: Optional[str] = None, no_progcache: bool = False,
          metrics_out: Optional[str] = None, log=print) -> dict:
    """Run (or resume) a serve loop to ``max_rounds``; returns the final
    serve record (also written to ``result_path`` when given).

    ``progcache_dir`` roots the AOT program cache (default
    ``<ckpt_dir>/progcache``; ``no_progcache=True`` disables it); jax's
    persistent compilation cache is the caller's to turn on
    (`progcache.enable_compile_cache`, as `main` does).  ``metrics_out``
    appends a crash-safe JSONL metrics line per round (`MetricsSink`)."""
    if chunk < 1:
        raise SystemExit(f"--chunk must be >= 1, got {chunk}")
    spans_before, traces_before = spans.snapshot(), rounds.trace_counts()
    exp = get_experiment(exp_name)
    cell = exp.cell(cell_name)
    prob = build_problem(exp.problem)
    if isinstance(prob, StreamProblem):
        return _serve_cohort(
            exp, cell, prob, seed=seed, chunk=chunk, max_rounds=max_rounds,
            ckpt_dir=ckpt_dir, backend=backend, keep=keep, plan=plan,
            crash_after_round=crash_after_round, result_path=result_path,
            progcache_dir=progcache_dir, no_progcache=no_progcache,
            metrics_out=metrics_out, log=log)
    spec, batch, basisb = build_setup(exp, cell, prob)
    plan = plan if plan is not None else faults.FaultPlan(n=batch.n)
    if plan.n != batch.n:
        raise SystemExit(
            f"fault plan is for n={plan.n} clients, fleet has {batch.n}")
    if not plan.trivial and not getattr(spec, "supports_faults", False):
        raise SystemExit(
            f"method {cell.method!r} models a fully synchronous fleet and "
            "cannot absorb injected faults (MethodSpec.supports_faults is "
            "False) — drop the fault flags or serve a partial-participation "
            "cell (bl2/bl3) or fednl_bag")
    backend = _resolve_backend(cell, backend)
    sharded = backend == "fast+sharded"
    crash = (faults.CrashInjector(crash_after_round)
             if crash_after_round is not None else None)
    x0, x_star = prob.x0, prob.x_star

    config = serve_config(exp, cell, seed, backend, plan)
    digest = artifacts.config_digest(config)
    cache = _activate_progcache(ckpt_dir, progcache_dir, no_progcache, log)
    t0_wall = time.perf_counter()      # time-to-first-round starts here
    with spans.span("serve.warm"):
        template = rounds.init_serve_carry(spec, batch, basisb, x0,
                                           sharded=sharded)
        # resolve the chunk program BEFORE checkpoint restore: on a warm
        # restart the executable deserializes in milliseconds and the
        # first round starts compile-free
        rounds.warm_chunk_program(spec, batch, basisb, x0, template,
                                  min(chunk, max_rounds),
                                  jax.random.PRNGKey(seed), sharded=sharded)
    with spans.span("serve.restore"):
        ck = artifacts.load_checkpoint(ckpt_dir, config_digest=digest)
        resumed_from = None
        if ck is not None:
            t = int(ck["t"])
            carry = _restore_carry(ck, template)
            streams = {name: np.asarray(ck["streams"][name])
                       for name in _STREAMS}
            root_key = jnp.asarray(ck["root_key"])
            resumed_from = t
            log(f"[serve] {exp.name}/{cell.name}: resumed from checkpoint "
                f"at round {t} (config {digest})")
        else:
            t = 0
            carry = template
            streams = _empty_streams(prob.d)
            root_key = jax.random.PRNGKey(seed)
            log(f"[serve] {exp.name}/{cell.name}: fresh run (config "
                f"{digest})")

    sink = MetricsSink(metrics_out) if metrics_out else None
    f_star = batched._f_star(batch, x_star) if sink else None
    chunks_run = 0
    waited_total = 0.0
    ttfr_s = None
    while t < max_rounds:
        steps = min(chunk, max_rounds - t)
        if plan.trivial:
            avail, waited = None, 0.0
        else:
            avail, waited = plan.schedule(t, steps)
        # run_chunk DONATES the carry (its buffers back the next chunk's
        # output) — reassign, and only ever checkpoint the returned carry
        with spans.span("serve.dispatch", t=t, steps=steps):
            carry, ys = rounds.run_chunk(spec, batch, basisb, x0, carry, t,
                                         steps, root_key, avail=avail,
                                         sharded=sharded)
        streams = _append_chunk(streams, ys)
        t += steps
        chunks_run += 1
        waited_total += waited
        if ttfr_s is None:
            ttfr_s = time.perf_counter() - t0_wall
        if sink is not None:
            with spans.span("serve.sink"):
                gaps = spec.eval_streams(
                    batch, jnp.asarray(streams["eval_x"][-steps:]),
                    f_star)["gap"]
                sink.emit_chunk(
                    range(t - steps, t), np.asarray(gaps),
                    streams["events"][-steps:],
                    {leg: streams[f"led_{leg}"][-steps:]
                     for leg in comm.CommLedger.LEGS})
        n_deg = _degraded(streams["events"][-steps:])
        log(f"[serve] rounds {t - steps}..{t - 1} done"
            + (f", {n_deg} degraded" if n_deg else "")
            + (f", straggler wait {waited:.2f}s" if waited else ""))
        if crash is not None:
            # fires BEFORE the covering checkpoint: the chunk is lost and
            # the resume path must recompute it (the acceptance scenario)
            crash.maybe_crash(t - 1)
        with spans.span("serve.checkpoint"):
            with spans.span("ckpt.payload"):
                leaves = [np.asarray(leaf)
                          for leaf in jax.tree_util.tree_leaves(carry)]
            artifacts.save_checkpoint(
                ckpt_dir, t=t, carry_leaves=leaves, streams=streams,
                root_key=np.asarray(root_key), config_digest=digest,
                keep=keep)

    evals = spec.eval_streams(batch, jnp.asarray(streams["eval_x"]),
                              batched._f_star(batch, x_star))
    led_streams = comm.CommLedger(
        *(jnp.asarray(streams[f"led_{leg}"])
          for leg in comm.CommLedger.LEGS))
    hist = batched._history(evals, led_streams)
    hist.events = [int(e) for e in streams["events"]]
    record = {
        "schema": artifacts.SERVE_SCHEMA,
        "experiment": exp.name,
        "cell": cell.name,
        "seed": seed,
        "config_digest": digest,
        "config": config,
        "rounds": t,
        "history": {
            "gaps": [float(g) for g in hist.gaps],
            "up_bits": [float(b) for b in hist.up_bits],
            "down_bits": [float(b) for b in hist.down_bits],
            "legs": {leg: [float(v) for v in hist.legs[leg]]
                     for leg in comm.CommLedger.LEGS},
            "events": hist.events,
        },
        "degraded_rounds": _degraded(streams["events"]),
        # operational facts, outside the bit-exactness contract (the CI
        # smoke job compares records with "meta" stripped)
        "meta": {
            "backend": backend,
            "chunk": chunk,
            "chunks_run": chunks_run,
            "resumed_from": resumed_from,
            "straggler_wait_s": waited_total,
            "runtime_s": time.perf_counter() - t0_wall,
            "ttfr_s": ttfr_s,
            "progcache": cache.summary() if cache is not None else None,
            "server_eigh": _server_eigh(streams["events"], resumed_from),
            "spans": spans.since(spans_before),
            "retraces": _retraces(traces_before),
        },
    }
    if result_path:
        artifacts.write_json(result_path, record)
        log(f"[serve] result → {result_path}")
    log(f"[serve] {t} rounds, final gap {record['history']['gaps'][-1]:.3e}, "
        f"{record['degraded_rounds']} degraded round(s)")
    return record


def _build_plan(args, n: int) -> faults.FaultPlan:
    straggler = None
    if args.straggler_mean > 0.0:
        straggler = faults.StragglerModel(
            mean_s=args.straggler_mean, slow_frac=args.slow_frac,
            slow_factor=args.slow_factor, timeout_s=args.timeout,
            retries=args.retries, backoff=args.backoff)
    return faults.FaultPlan(
        n=n, dropout_p=args.dropout_p,
        outages=tuple(faults.Outage.parse(o) for o in args.outage),
        straggler=straggler, seed=args.fault_seed)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.fed_serve",
        description=__doc__.split("\n\n")[0])
    ap.add_argument("--exp", required=True,
                    help="registered experiment (e.g. fig4)")
    ap.add_argument("--cell", required=True,
                    help="cell within the experiment (e.g. BL2_tau_half)")
    ap.add_argument("--seed", type=int, default=0,
                    help="root PRNG seed (per-round keys fold in the round)")
    ap.add_argument("--chunk", type=int, default=25,
                    help="rounds per scan chunk / checkpoint interval")
    ap.add_argument("--max-rounds", type=int, default=200,
                    help="serve until this many total rounds")
    ap.add_argument("--ckpt-dir", default="runs/serve",
                    help="checkpoint directory (resume looks here)")
    ap.add_argument("--backend",
                    choices=("fast", "fast+sharded", "cohort",
                             "cohort+sharded"),
                    default=None, help="override the cell's engine backend "
                    "(cohort* for synthetic_stream cells)")
    ap.add_argument("--keep", type=int, default=3,
                    help="checkpoints retained after pruning")
    ap.add_argument("--result", default=None,
                    help="write the final serve record JSON here")
    ap.add_argument("--progcache-dir", default=None,
                    help="AOT program cache directory (default: "
                         "<ckpt-dir>/progcache)")
    ap.add_argument("--no-progcache", action="store_true",
                    help="disable the AOT program cache (live-compile the "
                         "serve programs)")
    ap.add_argument("--metrics-out", default=None,
                    help="append per-round JSONL metrics (round, gap, "
                         "events, per-leg ledger bits) to this file")
    # fault injection
    ap.add_argument("--dropout-p", type=float, default=0.0,
                    help="i.i.d. per-(client, round) dropout probability")
    ap.add_argument("--outage", action="append", default=[],
                    metavar="CLIENT:START:STOP",
                    help="deterministic outage window (repeatable)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="fault stream seed (independent of --seed)")
    ap.add_argument("--straggler-mean", type=float, default=0.0,
                    help="mean client response delay in s (0 = no "
                         "straggler model)")
    ap.add_argument("--timeout", type=float, default=0.25,
                    help="per-round response deadline in s")
    ap.add_argument("--retries", type=int, default=1,
                    help="extra attempts for timed-out clients")
    ap.add_argument("--backoff", type=float, default=2.0,
                    help="deadline multiplier per retry")
    ap.add_argument("--slow-frac", type=float, default=0.0,
                    help="fraction of persistently slow clients")
    ap.add_argument("--slow-factor", type=float, default=10.0,
                    help="delay multiplier for slow clients")
    # crash harness
    ap.add_argument("--crash-after-round", type=int, default=None,
                    help="SIGKILL self after this round is computed but "
                         "before its checkpoint (crash test harness; omit "
                         "on restart)")
    args = ap.parse_args(argv)

    progcache.enable_compile_cache()
    exp = get_experiment(args.exp)
    prob = build_problem(exp.problem)
    serve(exp_name=args.exp, cell_name=args.cell, seed=args.seed,
          chunk=args.chunk, max_rounds=args.max_rounds,
          ckpt_dir=args.ckpt_dir, backend=args.backend, keep=args.keep,
          plan=_build_plan(args, prob.n),
          crash_after_round=args.crash_after_round,
          result_path=args.result, progcache_dir=args.progcache_dir,
          no_progcache=args.no_progcache, metrics_out=args.metrics_out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
