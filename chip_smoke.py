"""Chip smoke test: serve the largest GLM deployment on a TPU and check it.

    python chip_smoke.py                # one chip: fig1-xl / BL1 on `fast`
    python chip_smoke.py --four-chips   # four chips: `fast+sharded` vs `fast`

One chip: serves fig1-xl / BL1 (512 clients, m=32, d=1200, r=32, data_outer
basis in §2.3 block mode, TopK k=r², float64) for 8 rounds in 2 chunks
through `repro.launch.fed_serve.serve`, then restarts warm in the same
process (checkpoints dropped, the AOT program cache kept) and serves again.
It checks that

  * the cold history matches the committed CPU artifact
    ``results/exp/fig1-xl/BL1.seed0.json``: per-round uplink/downlink bits
    and every ledger leg equal, gaps of rounds 0-2 within 1e-6 relative,
    and the gap at or below 1e-6 at round 3 (2.824192 Mbit/node);
  * the warm serve loads its programs from the cache (hits, no misses)
    and reproduces the cold history exactly.

Four chips: serves the same cell on `fast+sharded`, whose client mesh must
span all four chips with the client state split four ways, and on `fast`
on the first chip; bits must be equal and gaps within the same tolerance.
Whether the two histories are bitwise equal is printed, not asserted.

It exits non-zero, printing no result, when JAX finds no TPU.  Any failed
phase or check exits non-zero.  The last line of standard output is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Checkpoints and the program cache live under ``runs/chip_smoke`` in the
checkout; jax's compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, or else to the checkout's ``.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import types

ROOT = os.path.dirname(os.path.abspath(__file__))
EXP, CELL, ROUNDS, CHUNK = "fig1-xl", "BL1", 8, 4
ARTIFACT = os.path.join(ROOT, "results", "exp", EXP, f"{CELL}.seed0.json")
GAP_RTOL = 1e-6                 # rounds 0-2 against the reference
TOL, TOL_ROUND, TOL_MBITS = 1e-6, 3, 2.824192


def _say(*parts):
    print("[chip_smoke]", *parts, flush=True)


class _CompileClock:
    """Sums jax's backend-compile durations while it is open."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self._open = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self._open and event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def __enter__(self):
        self.seconds, self._open = 0.0, True
        return self

    def __exit__(self, *exc):
        self._open = False


def _fresh_ckpt_dir(path: str) -> str:
    """Remove old checkpoints under ``path``; keep its program cache."""
    os.makedirs(path, exist_ok=True)
    for name in os.listdir(path):
        if name != "progcache":
            full = os.path.join(path, name)
            shutil.rmtree(full) if os.path.isdir(full) else os.remove(full)
    return path


def _serve(backend: str, ckpt_dir: str) -> dict:
    from repro.launch import fed_serve

    return fed_serve.serve(exp_name=EXP, cell_name=CELL, seed=0,
                           chunk=CHUNK, max_rounds=ROUNDS,
                           ckpt_dir=_fresh_ckpt_dir(ckpt_dir),
                           backend=backend, log=_say)


def _check_against(hist: dict, ref: dict, what: str) -> list:
    """The checks of the module docstring; returns failure messages."""
    import numpy as np

    bad = []
    for key in ("up_bits", "down_bits"):
        if hist[key] != ref[key]:
            bad.append(f"{what}: {key} differ: {hist[key]} vs {ref[key]}")
    for leg, stream in ref["legs"].items():
        if hist["legs"][leg] != stream:
            bad.append(f"{what}: leg {leg} differs: {hist['legs'][leg]} "
                       f"vs {stream}")
    g, gr = np.asarray(hist["gaps"]), np.asarray(ref["gaps"])
    rel = np.abs(g[:TOL_ROUND] - gr[:TOL_ROUND]) / np.abs(gr[:TOL_ROUND])
    _say(f"{what}: gap relative error rounds 0-2: {rel.tolist()}")
    if not (rel <= GAP_RTOL).all():
        bad.append(f"{what}: gaps of rounds 0-2 off by {rel.tolist()} "
                   f"(limit {GAP_RTOL})")
    if not g[TOL_ROUND] <= TOL:
        bad.append(f"{what}: gap at round {TOL_ROUND} is {g[TOL_ROUND]}, "
                   f"not <= {TOL}")
    from repro.exp import bits_to_tol

    b = bits_to_tol(types.SimpleNamespace(**hist), TOL)
    _say(f"{what}: Mbit/node to gap {TOL}: {b.mbits} (reached={b.reached})")
    if not (b.reached and b.mbits == TOL_MBITS):
        bad.append(f"{what}: bits to {TOL} are {b.mbits} Mbit/node, not "
                   f"{TOL_MBITS}")
    return bad


def _time_rounds(prob, sharded: bool) -> float:
    """Seconds per round over two warm chunks, each ending in
    block_until_ready (the programs are already compiled and memoized)."""
    import jax

    from repro.core import rounds
    from repro.exp.registry import get_experiment
    from repro.launch import fed_serve

    exp = get_experiment(EXP)
    spec, batch, basisb = fed_serve.build_setup(exp, exp.cell(CELL), prob)
    key = jax.random.PRNGKey(0)
    carry = rounds.init_serve_carry(spec, batch, basisb, prob.x0,
                                    sharded=sharded)
    carry, ys = rounds.run_chunk(spec, batch, basisb, prob.x0, carry, 0,
                                 CHUNK, key, sharded=sharded)
    jax.block_until_ready((carry, ys))
    t0 = time.perf_counter()
    for t in (CHUNK, 2 * CHUNK):
        carry, ys = rounds.run_chunk(spec, batch, basisb, prob.x0, carry, t,
                                     CHUNK, key, sharded=sharded)
    jax.block_until_ready((carry, ys))
    return (time.perf_counter() - t0) / (2 * CHUNK)


def _peak_bytes(dev) -> object:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def one_chip(dev, clock) -> list:
    from repro.core import progcache, rounds
    from repro.exp import build_problem, get_experiment

    t0 = time.perf_counter()
    with clock:
        prob = build_problem(get_experiment(EXP).problem)
        prob.x_star.block_until_ready()
    _say(f"problem build: {time.perf_counter() - t0:.3f} s "
         f"(compile {clock.seconds:.3f} s)")

    ckpt = os.path.join(ROOT, "runs", "chip_smoke")
    with clock:
        cold = _serve("fast", ckpt)
    meta = cold["meta"]
    _say(f"cold serve: ttfr_s {meta['ttfr_s']:.3f}, runtime_s "
         f"{meta['runtime_s']:.3f}, compile {clock.seconds:.3f} s, "
         f"progcache {meta['progcache']['stats']}")
    _say(f"gaps: {cold['history']['gaps']}")
    _say(f"wall time per round (warm, block_until_ready): "
         f"{_time_rounds(prob, sharded=False):.6f} s")
    _say(f"peak_bytes_in_use: {_peak_bytes(dev)}")

    # a restarted server: no in-process executables, a fresh cache handle
    rounds.clear_aot_memo()
    progcache.deactivate()
    warm = _serve("fast", ckpt)
    wmeta = warm["meta"]
    stats = wmeta["progcache"]["stats"]
    _say(f"warm serve: ttfr_s {wmeta['ttfr_s']:.3f}, runtime_s "
         f"{wmeta['runtime_s']:.3f}, progcache {stats}")

    with open(ARTIFACT) as f:
        ref = json.load(f)["history"]
    bad = _check_against(cold["history"], ref, "cold vs CPU artifact")
    if not stats.get("hit") or stats.get("miss"):
        bad.append(f"warm serve did not load every program from the "
                   f"cache: {stats}")
    if warm["history"] != cold["history"]:
        bad.append("warm history differs from the cold one")
    return bad


def four_chips(devs) -> list:
    import jax

    from repro.exp import build_problem, get_experiment
    from repro.launch.mesh import make_client_mesh

    exp = get_experiment(EXP)
    _, ndev = make_client_mesh(exp.problem.n_clients)
    _say(f"client mesh: {ndev} devices for {exp.problem.n_clients} clients")
    if ndev != 4:
        return [f"client mesh spans {ndev} devices, not 4"]
    prob = build_problem(exp.problem)
    runs = {}
    for backend in ("fast+sharded", "fast"):
        t0 = time.perf_counter()
        rec = _serve(backend, os.path.join(ROOT, "runs", "chip_smoke_4",
                                           backend.replace("+", "_")))
        runs[backend] = rec["history"]
        _say(f"{backend}: wall {time.perf_counter() - t0:.3f} s, ttfr_s "
             f"{rec['meta']['ttfr_s']:.3f}, gaps {rec['history']['gaps']}")
        _say(f"{backend}: wall time per round (warm, block_until_ready): "
             f"{_time_rounds(prob, sharded=backend != 'fast'):.6f} s")

    # the sharded carry's client-stacked leaves must sit a quarter on each chip
    from repro.core import rounds
    from repro.launch import fed_serve

    spec, batch, basisb = fed_serve.build_setup(exp, exp.cell(CELL), prob)
    carry = rounds.init_serve_carry(spec, batch, basisb, prob.x0,
                                    sharded=True)
    split = [(leaf.shape, leaf.sharding.shard_shape(leaf.shape),
              len(leaf.sharding.device_set))
             for leaf in jax.tree_util.tree_leaves(carry)
             if leaf.ndim and leaf.shape[0] == batch.n]
    _say(f"client-stacked carry leaves (shape, shard shape, devices): "
         f"{split}")
    _say("bytes_in_use per chip: "
         f"{[(d.memory_stats() or {}).get('bytes_in_use') for d in devs]}")
    bad = []
    if not split or any(s[0] != batch.n // 4 or k != 4 for _, s, k in split):
        bad.append(f"client state is not split four ways: {split}")
    bad += _check_against(runs["fast+sharded"], runs["fast"],
                          "fast+sharded vs fast")
    _say(f"bitwise equal histories (fast+sharded vs fast): "
         f"{runs['fast+sharded'] == runs['fast']}")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="compare fast+sharded over four chips with fast")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's devices are {devs[0].platform} "
              f"({len(devs)} x {devs[0].device_kind})", file=sys.stderr)
        return 2
    _say(f"platform {devs[0].platform}, device_kind {devs[0].device_kind}, "
         f"device count {len(devs)}")
    if args.four_chips and len(devs) != 4:
        print(f"chip_smoke: --four-chips needs 4 chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import progcache

    _say(f"compile cache: {progcache.enable_compile_cache()}")
    clock = _CompileClock()
    bad = four_chips(devs) if args.four_chips else one_chip(devs[0], clock)
    for msg in bad:
        print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    if bad:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
