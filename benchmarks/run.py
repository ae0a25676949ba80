"""Benchmark harness (deliverable d): one entry per paper table/figure plus
kernel micro-benches.  Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig1r1
  PYTHONPATH=src python -m benchmarks.run --only fig1r1 --json

The paper-figure benches are thin wrappers over the declarative experiment
registry (`repro.exp`): each pulls its method/compressor/basis cells from
the registered experiment and times/evaluates them through the same
`run_cell` engine the figure CSVs come from — there is exactly one place a
figure's configuration lives.

`derived` encodes the figure's headline quantity — for the convergence
figures that is Mbits/node to reach gap 1e-6 (the paper's x-axis) plus an
explicit ``reached=`` flag (an ``inf`` alone cannot distinguish "diverged"
from "stopped early"; the flag also lands in the JSON record's ``extra``
field so BENCH trajectories can tell the two apart), for kernels GFLOP/s
(interpret-mode: correctness-path timing only).

``--json`` additionally writes one ``BENCH_<name>.json`` perf record per
bench group (per-bench µs + derived metric + extras, plus an
``environment`` block — jax/jaxlib versions, backend, device population —
so records from different machines are comparable), seeding the repo's
benchmark trajectory; ``--json-dir`` picks the output directory.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, reps=3):
    fn()  # warm/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6  # µs


def _child_env() -> dict:
    """Environment for a bench group's child process, which runs its own
    JAX on the parent's platform.  On an accelerator the parent already
    holds the chip, so a child could not reach it: those groups refuse to
    start there instead of timing something else."""
    platform = jax.default_backend()
    if platform != "cpu":
        raise RuntimeError(
            f"this bench group starts child JAX processes, which cannot "
            f"use the {platform} while this process holds it; run the "
            "group with JAX_PLATFORMS=cpu")
    return dict(os.environ, PYTHONPATH="src")


def _mbits(hist, tol=1e-6):
    """Headline metric string + extra dict, via the shared `repro.exp`
    helper (one implementation for benches, sweeps and artifacts — the old
    local copy returned a bare ``inf`` with no reached flag)."""
    from repro.exp import bits_to_tol

    b = bits_to_tol(hist, tol)
    return (f"Mbits_to_{tol:g}={b.mbits:.3f};reached={b.reached}",
            {"mbits_to_tol": None if not b.reached else b.mbits,
             "reached": b.reached})


def _exp(name):
    """(experiment, built problem) for a registered `repro.exp` experiment."""
    from repro.exp import build_problem, get_experiment

    exp = get_experiment(name)
    return exp, build_problem(exp.problem)


BENCHES = {}


def bench(name):
    def deco(fn):
        BENCHES[name] = fn
        return fn
    return deco


# ---------------- paper figures (comm complexity) ---------------------------
@bench("fig1r1_BL1_vs_FedNL")
def fig1r1():
    from repro.exp import run_cell
    exp, prob = _exp("fig1r1")
    STEPS = 3

    def runner(cell_name, backend):
        cell = exp.cell(cell_name)
        return lambda: run_cell(exp, cell, prob, steps=STEPS, backend=backend)

    t_bl = _timeit(runner("BL1", "fast"), reps=3)
    t_bl_ref = _timeit(runner("BL1", "reference"), reps=1)
    t_fn = _timeit(runner("FedNL", "fast"), reps=3)    # FedNL timed on its own config
    h_bl = run_cell(exp, exp.cell("BL1"), prob, steps=18)
    h_fn = run_cell(exp, exp.cell("FedNL"), prob, steps=18)
    d_bl, x_bl = _mbits(h_bl)
    d_fn, x_fn = _mbits(h_fn)
    return [("fig1r1_BL1", t_bl / STEPS, d_bl, x_bl),
            ("fig1r1_BL1_reference", t_bl_ref / STEPS,
             f"fast_speedup={t_bl_ref / t_bl:.1f}x"),
            ("fig1r1_FedNL", t_fn / STEPS, d_fn, x_fn)]


@bench("fig1r2_BL1_vs_first_order")
def fig1r2():
    from repro.exp import run_cell
    exp, prob = _exp("fig1r2")
    rows = []
    for cell_name, steps in (("BL1", 18), ("GD", 150), ("DIANA", 150)):
        h = run_cell(exp, exp.cell(cell_name), prob, steps=steps)
        derived, extra = _mbits(h)
        rows.append((f"fig1r2_{cell_name}", 0.0, derived, extra))
    return rows


@bench("fig2_newton_basis")
def fig2():
    from repro.exp import run_cell
    exp, prob = _exp("fig2")
    h1 = run_cell(exp, exp.cell("newton_std"), prob)
    h2 = run_cell(exp, exp.cell("newton_basis"), prob)
    per1 = h1.up_bits[2] - h1.up_bits[1]
    per2 = h2.up_bits[2] - h2.up_bits[1]
    return [("fig2_newton_std", 0.0, f"bits_per_iter={per1:.0f}"),
            ("fig2_newton_basis", 0.0,
             f"bits_per_iter={per2:.0f};saving={per1/per2:.2f}x")]


@bench("fig4_partial_participation")
def fig4():
    from repro.exp import run_cell
    exp, prob = _exp("fig4")
    out = []
    for tag, tau in (("full", 10), ("half", 5)):
        h = run_cell(exp, exp.cell(f"BL2_tau_{tag}"), prob, steps=80)
        derived, extra = _mbits(h)
        out.append((f"fig4_BL2_tau{tau}", 0.0, derived, extra))
    return out


@bench("fig5_bidirectional")
def fig5():
    from repro.exp import run_cell
    exp, prob = _exp("fig5")
    # the registry's BL1-BC cell is the convergent bidirectional config
    # (K=r both ways, p=1/2; the paper's most aggressive A.7 setting
    # diverges on this harder synthetic instance)
    h = run_cell(exp, exp.cell("BL1-BC"), prob, steps=60)
    derived, extra = _mbits(h)
    return [("fig5_BL1_BC", 0.0, derived, extra)]


@bench("fig6_bl2_vs_bl3")
def fig6():
    from repro.exp import run_cell
    exp, prob = _exp("fig6")
    h2 = run_cell(exp, exp.cell("BL2_p1.00"), prob, steps=30)
    h3 = run_cell(exp, exp.cell("BL3_p1.00"), prob, steps=30)
    return [("fig6_BL2_std", 0.0, f"gap@30={h2.gaps[-1]:.2e}"),
            ("fig6_BL3", 0.0, f"gap@30={h3.gaps[-1]:.2e}")]


@bench("basis_matrix")
def basis_matrix():
    """The paper's thesis as one grid: bits-to-ε for every REGISTERED basis
    × {Top-K, Rank-R} on BL1, one-time basis shipment included (the ledger's
    basis_ship leg is broken out in `derived`).  Every basis gets the SAME
    coefficient budget (K = r² — the data basis's full coefficient count),
    so differences are purely where the basis concentrates energy."""
    from repro.core import bl
    from repro.core.basis import available_bases, is_pytree_basis, make_bases
    from repro.core.compressors import Identity, RankR, TopK

    from repro.exp import build_problem, get_experiment

    prob = build_problem(get_experiment("fig1r1").problem)
    clients, x0, xs = prob.clients, prob.x0, prob.x_star
    r = 24
    STEPS = 16
    comps = {"topk": TopK(k=r * r), "rankr": RankR(r=2)}
    rows = []
    for bname in available_bases():
        if bname == "psd" or is_pytree_basis(bname):
            # psd is BL3's basis (Example 5.1); pytree bases (per_layer_svd)
            # are the DNN workload's — see the fed_dnn bench
            continue
        bases = make_bases(bname, clients, x0=x0)
        for cname, comp in comps.items():
            h = bl.bl1(clients, bases, [comp for _ in clients], Identity(),
                       x0, xs, STEPS, backend="fast")
            ship = h.legs["basis_ship"][-1] / 1e6
            derived, extra = _mbits(h)
            rows.append((
                f"basis_matrix_{bname}_{cname}", 0.0,
                f"{derived};gap@{STEPS}={h.gaps[-1]:.2e}"
                f";basis_ship_Mbits={ship:.3f}", extra))
    return rows


@bench("basis_ship")
def basis_ship():
    """The ISSUE's headline grid: basis × shipment wire × refresh period →
    bits-to-tol on the fig-dnn problem.  The question the grid answers is
    whether the per-layer SVD basis can HOLD its rounds-to-accuracy win
    once the one-time (U_ℓ, V_ℓ) shipment is billed: compressed wires
    (bf16/int8) shrink the basis_ship leg 2–4×, amortized refresh re-bills
    it on a drift trigger, and the structured DCT/Hadamard rotations ship
    zero floats by construction.  Each row records total Mbits-to-tol plus
    the basis_ship share so the trade is auditable.  ``REPRO_BENCH_TINY=1``
    shrinks to 3 cells at smoke depth for CI."""
    from repro.exp import build_problem, get_experiment
    from repro.fed import bldnn as B

    prob = build_problem(get_experiment("fig-dnn").problem)
    tiny = os.environ.get("REPRO_BENCH_TINY", "0") == "1"
    STEPS = 6 if tiny else 40
    TOL = 0.1   # fig-dnn's tolerance: training error ≤ 10%
    cells = [
        ("topk_nobasis", dict(use_basis=False)),
        ("svd_f32", {}),
        ("svd_bf16", dict(ship_float_bits=16)),
        ("svd_int8", dict(ship_float_bits=8)),
        ("svd_int8_T5", dict(ship_float_bits=8, rounds_per_refresh=5,
                             drift_threshold=0.05)),
        ("dct_tree", dict(basis_kind="dct_tree")),
        ("hadamard_tree", dict(basis_kind="hadamard_tree")),
    ]
    if tiny:
        cells = [cells[0], cells[3], cells[5]]
    rows = []
    for tag, kw in cells:
        cfg = B.BLDNNConfig(lr=0.05, top_k_frac=0.1, **kw)
        h = B.run_bldnn(prob.loss_fn, prob.eval_fn, prob.params0,
                        prob.batch, STEPS, cfg)
        derived, extra = _mbits(h, tol=TOL)
        ship = h.legs["basis_ship"][-1] / 1e6
        extra.update(basis_ship_mbits=ship, gap_end=float(h.gaps[-1]))
        rows.append((f"basis_ship_{tag}", 0.0,
                     f"{derived};basis_ship_Mbits={ship:.3f}"
                     f";gap@{STEPS}={h.gaps[-1]:.3f}", extra))
    return rows


#: per-round cost of the retired hand-rolled BL-DNN shard_map loop
#: (`fed.bldnn.make_fed_train_step`, one jitted step dispatched per round
#: over an 8-virtual-device mesh), measured on the fig-dnn problem in the
#: commit that deleted it — the engine rows below are re-measured live
#: against this frozen baseline.
_FED_DNN_LEGACY_US = 19162.0


@bench("fed_dnn")
def fed_dnn():
    """BL-DNN round cost on the pytree engine (the fig-dnn problem):
    single-device chunked scan (with and without the post-scan trajectory
    evaluation) and the 8-virtual-device client-sharded backend — exact
    (fixed-order gather, bitwise-checked against the fast path) and
    exact=False (BLDNNSpec's pmean ReducePlan) — vs the retired
    hand-rolled loop's recorded per-round cost (subprocess: the device
    count is locked at first jax init here)."""
    import subprocess
    import sys

    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time
import jax
from repro.core import rounds
from repro.fed import bldnn as B
from repro.exp import build_problem, get_experiment

exp = get_experiment("fig-dnn")
prob = build_problem(exp.problem)
cfg = B.BLDNNConfig(lr=0.05, top_k_frac=0.1)
STEPS = 40

from repro.core.basis import per_layer_svd_basis
spec = B.build_spec(prob.loss_fn, prob.eval_fn, prob.params0, cfg)
basis = per_layer_svd_basis(prob.params0)
root = jax.random.PRNGKey(0)

def scan_run():
    # chunked driver without the trajectory eval (each rep starts from a
    # fresh carry, so it pays the cheap carry init too)
    c = rounds.init_serve_carry(spec, prob.batch, basis, prob.params0)
    c, ys = rounds.run_chunk(spec, prob.batch, basis, prob.params0, c, 0,
                             STEPS, root)
    jax.block_until_ready((c, ys))

def e2e(backend, exact=True):
    return lambda: B.run_bldnn(prob.loss_fn, prob.eval_fn, prob.params0,
                               prob.batch, STEPS, cfg, backend=backend,
                               exact=exact)

hists = {}
for name, fn in (("scan_only", scan_run), ("fast", e2e("fast")),
                 ("sharded", e2e("fast+sharded")),
                 ("sharded_approx", e2e("fast+sharded", exact=False))):
    hists[name] = fn()   # warm/compile (History for the e2e rows)
    t0 = time.perf_counter()
    for _ in range(3):
        fn()
    print(f"RESULT {name} {(time.perf_counter() - t0) / 3 / STEPS * 1e6:.1f}")
bw = (hists["sharded"].gaps == hists["fast"].gaps
      and hists["sharded"].up_bits == hists["fast"].up_bits)
print(f"BITWISE {bw}")
"""
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=900, env=_child_env())
    res, bw = {}, None
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT"):
            _, name, us = line.split()
            res[name] = float(us)
        elif line.startswith("BITWISE"):
            bw = line.split()[1] == "True"
    if set(res) != {"scan_only", "fast", "sharded", "sharded_approx"}:
        raise RuntimeError(proc.stdout + proc.stderr[-2000:])
    speedup = _FED_DNN_LEGACY_US / res["scan_only"]
    return [
        ("fed_dnn_engine_scan", res["scan_only"],
         f"per_round;old_loop_us={_FED_DNN_LEGACY_US:.0f}"
         f";speedup_vs_old_loop={speedup:.2f}x",
         {"old_loop_us_per_round": _FED_DNN_LEGACY_US,
          "speedup_vs_old_loop": speedup}),
        ("fed_dnn_engine_e2e", res["fast"],
         "per_round;includes_trajectory_eval"),
        ("fed_dnn_engine_sharded_8dev", res["sharded"],
         f"per_round;overhead_vs_fast={res['sharded'] / res['fast']:.2f}x"
         f";bitwise_equal_histories={bw}",
         {"overhead_vs_fast": res["sharded"] / res["fast"],
          "bitwise_equal_histories": bw}),
        ("fed_dnn_engine_sharded_8dev_approx", res["sharded_approx"],
         f"per_round;overhead_vs_fast="
         f"{res['sharded_approx'] / res['fast']:.2f}x;exact=False",
         {"overhead_vs_fast": res["sharded_approx"] / res["fast"]}),
    ]


_ENGINE_GRID_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=@NDEV@"
import time
import jax, jax.numpy as jnp
from repro.core import bl, glm
from repro.core.basis import orth_basis_from_data
from repro.core.compressors import Identity, TopK

TINY = @TINY@
# STEPS=24 amortizes the one-time init/dispatch cost so per_round reflects
# the steady-state marginal rate (at STEPS=6 the fixed ~10ms still dominates)
m, d, r, STEPS, REPS = (20, 24, 8, 3, 1) if TINY else (60, 120, 24, 24, 2)
clients = glm.make_synthetic(seed=0, n_clients=8, m=m, d=d, r=r, lam=1e-3)
x0 = jnp.zeros(d, jnp.float64)
xs = glm.newton_solve(clients, x0, 20)
bases = [orth_basis_from_data(c.A) for c in clients]
k = bases[0].r

def time_cell(tag, fn, steps):
    h = fn()   # warm/compile
    t0 = time.perf_counter()
    for _ in range(REPS):
        fn()
    us = (time.perf_counter() - t0) / REPS / steps * 1e6
    print(f"RESULT {tag} {us:.1f}", flush=True)
    return h

def run_bl1(backend, exact=True):
    return bl.bl1(clients, bases, [TopK(k=k)] * 8, Identity(), x0, xs,
                  STEPS, backend=backend, exact=exact)

h_fast = time_cell("bl1_fast", lambda: run_bl1("fast"), STEPS)
h_ex = time_cell("bl1_sharded", lambda: run_bl1("fast+sharded"), STEPS)
time_cell("bl1_sharded_approx",
          lambda: run_bl1("fast+sharded", exact=False), STEPS)
bw = (h_ex.gaps == h_fast.gaps and h_ex.up_bits == h_fast.up_bits
      and h_ex.down_bits == h_fast.down_bits)
print(f"BITWISE bl1 {bw}", flush=True)

if not TINY:
    from repro.fed import bldnn as B
    from repro.exp import build_problem, get_experiment
    prob = build_problem(get_experiment("fig-dnn").problem)
    cfg = B.BLDNNConfig(lr=0.05, top_k_frac=0.1)
    DSTEPS = 12

    def run_dnn(backend, exact=True):
        return B.run_bldnn(prob.loss_fn, prob.eval_fn, prob.params0,
                           prob.batch, DSTEPS, cfg, backend=backend,
                           exact=exact)

    h_fast = time_cell("bldnn_fast", lambda: run_dnn("fast"), DSTEPS)
    h_ex = time_cell("bldnn_sharded", lambda: run_dnn("fast+sharded"),
                     DSTEPS)
    time_cell("bldnn_sharded_approx",
              lambda: run_dnn("fast+sharded", exact=False), DSTEPS)
    bw = h_ex.gaps == h_fast.gaps and h_ex.up_bits == h_fast.up_bits
    print(f"BITWISE bldnn {bw}", flush=True)
"""


@bench("engine_sharded")
def engine_sharded():
    """Round-engine aggregation grid: method {BL1, BL-DNN} × device count
    {4, 8} × collective mode {exact fixed-order gather, exact=False ring
    psum/pmean per the spec's ReducePlan}, each against the single-device
    vmap baseline measured in the same subprocess (device count is locked
    at first jax init, so each mesh size gets its own child).  Exact-mode
    rows carry an ACTUAL bitwise-equality verdict, not an assumption.  On
    one physical CPU the sharded backend pays collective + replication
    overhead; these rows track that tax.  ``REPRO_BENCH_TINY=1`` shrinks
    the grid (8-device BL1 only, tiny sizes) for CI smoke."""
    import subprocess
    import sys

    tiny = os.environ.get("REPRO_BENCH_TINY", "0") == "1"
    env = _child_env()
    rows = []
    for ndev in ((8,) if tiny else (8, 4)):
        script = (_ENGINE_GRID_SCRIPT.replace("@NDEV@", str(ndev))
                  .replace("@TINY@", str(tiny)))
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, timeout=900,
                              env=env)
        res, bitwise = {}, {}
        for line in proc.stdout.splitlines():
            if line.startswith("RESULT"):
                _, tag, us = line.split()
                res[tag] = float(us)
            elif line.startswith("BITWISE"):
                _, meth, flag = line.split()
                bitwise[meth] = flag == "True"
        want = {"bl1_fast", "bl1_sharded", "bl1_sharded_approx"}
        if not tiny:
            want |= {"bldnn_fast", "bldnn_sharded", "bldnn_sharded_approx"}
        if set(res) != want:
            raise RuntimeError(proc.stdout + proc.stderr[-2000:])
        for meth in ("bl1",) if tiny else ("bl1", "bldnn"):
            fast = res[f"{meth}_fast"]
            if ndev == 8:   # one baseline row per method (mesh-independent)
                rows.append((f"engine_{meth}_fast_8clients", fast,
                             "per_round;single_device_baseline"))
            for mode, suffix in (("sharded", ""), ("sharded_approx",
                                                   "_approx")):
                us = res[f"{meth}_{mode}"]
                tax = us / fast
                derived = (f"per_round;ndev={ndev}"
                           f";overhead_vs_fast={tax:.2f}x")
                extra = {"ndev": ndev, "overhead_vs_fast": tax}
                if suffix:
                    derived += ";exact=False"
                else:
                    derived += (";bitwise_equal_histories="
                                f"{bitwise[meth]}")
                    extra["bitwise_equal_histories"] = bitwise[meth]
                rows.append((f"engine_{meth}_sharded_{ndev}dev{suffix}",
                             us, derived, extra))
    return rows


_COHORT_STREAM_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import time
import numpy as np
import jax, jax.numpy as jnp
from repro.core import cohort, client_batch, rounds, compressors, specs

TINY = @TINY@
d, m = 24, 8
COHORT = 64 if TINY else 256
RPC = 4
ROUNDS = 8 if TINY else 16
NS = (512, 2048) if TINY else (1000, 10000, 100000)
N_PARITY = 64 if TINY else 256
x0 = jnp.zeros(d, jnp.float64)
key = jax.random.PRNGKey(0)

def bl2(n, tau):
    bb = cohort.standard_basisb(d, n)
    return specs.BL2Spec(
        hess_comp=compressors.TopK(k=2 * d),
        model_comp=compressors.Identity(),
        alpha=1.0, eta=1.0, p=1.0, tau=tau, init_exact=True,
        init_hess_bits=bb.init_coeff_bits_mean(True),
        basis_bits=bb.transmission_bits_mean(), block=False)

# flat-in-n: the SAME cohort/epoch geometry at every fleet size, so the
# jitted chunk program (shapes keyed on the cohort capacity) is shared and
# the only n-dependence left is the engine's host plane
for n in NS:
    store = client_batch.synthetic_store(0, n, m, d, lam=1e-3)
    eng = cohort.CohortEngine(bl2(n, COHORT // 2), store, x0, cohort=COHORT,
                              rounds_per_cohort=RPC, root_key=key,
                              basis="standard")
    jax.block_until_ready(eng.run_chunk(0, ROUNDS))       # warm/compile
    t0 = time.perf_counter()
    jax.block_until_ready(eng.run_chunk(ROUNDS, ROUNDS))
    us = (time.perf_counter() - t0) / ROUNDS * 1e6
    print(f"RESULT n{n} {us:.1f}", flush=True)
    print(f"OVERLAP n{n} {eng.prefetch_overlap:.4f}", flush=True)
    eng.close()

# cohort==fleet: the engine factory's pick, bitwise the stacked engine,
# both reducers
from repro.exp import engine, registry

for sharded, tag in ((False, "vmap"), (True, "sharded")):
    n = N_PARITY
    cell = registry.MethodCell(
        "BL2", "bl2", 6, basis="standard",
        hess_comp=registry.CompressorCfg(kind="topk", k=2 * d),
        model_comp=registry.CompressorCfg(kind="identity"), backend="cohort",
        params=(("tau", n // 2), ("cohort", n), ("rounds_per_cohort", 2)))
    pspec = registry.ProblemSpec(kind="synthetic_stream", n_clients=n, m=m,
                                 d=d, lam=1e-3)
    exp = registry.Experiment(name="cohort-fleet", figure="extra",
                              title="cohort == fleet", paper_ref="bench",
                              cells=(cell,), problem=pspec)
    store = client_batch.synthetic_store(0, n, m, d, lam=1e-3)
    prob = engine.StreamProblem(spec=pspec, store=store, x0=x0, x_star=None)
    eng = engine.make_engine(exp, cell, prob, root_key=key, sharded=sharded)
    batch = store.gather_batch(np.arange(n))
    bb = cohort.standard_basisb(d, n)
    c0 = rounds.init_serve_carry(eng.spec, batch, bb, x0, sharded=sharded)
    _, ys1 = rounds.run_chunk(eng.spec, batch, bb, x0, c0, 0, 6, key,
                              sharded=sharded)
    ys2 = eng.run_chunk(0, 6)
    eng.close()
    eq = all(np.array_equal(np.asarray(a), np.asarray(b))
             for a, b in zip(jax.tree_util.tree_leaves(ys1),
                             jax.tree_util.tree_leaves(ys2)))
    print(f"BITWISE {tag} {eq}", flush=True)
"""


@bench("cohort_stream")
def cohort_stream():
    """Cohort-streaming engine (`repro.core.cohort`): per-round wall time
    vs fleet size at FIXED cohort geometry — the tentpole headline is that
    rounds are flat in n (the device only ever sees the cohort; the host
    plane is O(cohort) per epoch), pinned at ≤1.15× from the smallest to
    the largest fleet.  Also records the measured prefetch overlap (the
    fraction of next-epoch gather+H2D hidden behind the chunk scan) and an
    ACTUAL cohort==fleet bitwise-parity verdict against the stacked engine
    on both reducers.  ``REPRO_BENCH_TINY=1`` shrinks fleets for CI smoke
    (subprocess: the sharded parity leg needs the 8-device mesh, and the
    device count is locked at first jax init)."""
    import subprocess
    import sys

    tiny = os.environ.get("REPRO_BENCH_TINY", "0") == "1"
    env = _child_env()
    script = _COHORT_STREAM_SCRIPT.replace("@TINY@", str(tiny))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    res, overlap, bitwise = {}, {}, {}
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT"):
            _, tag, us = line.split()
            res[tag] = float(us)
        elif line.startswith("OVERLAP"):
            _, tag, frac = line.split()
            overlap[tag] = float(frac)
        elif line.startswith("BITWISE"):
            _, tag, flag = line.split()
            bitwise[tag] = flag == "True"
    ns = (512, 2048) if tiny else (1000, 10000, 100000)
    if set(res) != {f"n{n}" for n in ns} or set(bitwise) != {"vmap",
                                                             "sharded"}:
        raise RuntimeError(proc.stdout + proc.stderr[-2000:])
    rows = []
    for n in ns:
        rows.append((f"cohort_stream_n{n}", res[f"n{n}"],
                     f"per_round;fleet={n};prefetch_overlap="
                     f"{overlap[f'n{n}']:.2f}",
                     {"n_clients": n,
                      "prefetch_overlap": overlap[f"n{n}"]}))
    flat = res[f"n{ns[-1]}"] / res[f"n{ns[0]}"]
    rows.append((
        "cohort_stream_flatness", 0.0,
        f"per_round_ratio_n{ns[-1]}_vs_n{ns[0]}={flat:.3f}x"
        f";bitwise_vmap={bitwise['vmap']}"
        f";bitwise_sharded={bitwise['sharded']}",
        {"flatness_ratio": flat, "n_small": ns[0], "n_large": ns[-1],
         "bitwise_equal_histories_vmap": bitwise["vmap"],
         "bitwise_equal_histories_sharded": bitwise["sharded"]}))
    return rows


@bench("cold_start")
def cold_start():
    """Cold vs warm-restart time-to-first-round through the two-tier
    program cache (`repro.core.progcache`): each backend serves a short
    run twice in fresh subprocesses against the SAME checkpoint directory
    — the cold child compiles and populates ``<ckpt>/progcache``, then its
    checkpoints are deleted (cache kept) and the warm child replays the
    identical run from deserialized executables.  Rows report both TTFRs,
    the speedup, and an ACTUAL bitwise-equality verdict over the full
    served histories (gaps + per-leg ledger bits + events), plus the warm
    child's hit/miss counters — a warm run that silently recompiles
    (fingerprint drift across processes) fails the bench rather than
    reporting a ~1x speedup.  ``REPRO_BENCH_TINY=1`` shrinks the round
    budget for CI smoke."""
    import shutil
    import subprocess
    import sys
    import tempfile

    tiny = os.environ.get("REPRO_BENCH_TINY", "0") == "1"
    max_rounds, chunk = (4, 2) if tiny else (12, 6)
    grid = (
        ("stacked", "fig4", "BL2_tau_half", "fast", None),
        ("sharded", "fig4", "BL2_tau_half", "fast+sharded", 8),
        ("cohort", "cohort-smoke", "BL2", None, None),
    )
    rows = []
    for name, exp, cell, backend, ndev in grid:
        work = tempfile.mkdtemp(prefix=f"bench_cold_start_{name}_")
        ckpt = os.path.join(work, "ckpt")
        env = _child_env()
        # a compile cache of this run's own, so the cold child really
        # compiles (the checkout's shared cache may already hold the
        # programs); the warm child reads the same one
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(work, "xla")
        if ndev:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                                + f" --xla_force_host_platform_device_count"
                                  f"={ndev}")
        try:
            recs = {}
            for phase in ("cold", "warm"):
                if phase == "warm":
                    # drop the checkpoints (else the warm child resumes a
                    # finished run and serves 0 rounds) but keep the
                    # progcache subdirectory they sit next to
                    for f in os.listdir(ckpt):
                        path = os.path.join(ckpt, f)
                        if os.path.isfile(path):
                            os.remove(path)
                res = os.path.join(work, f"{phase}.json")
                cmd = [sys.executable, "-m", "repro.launch.fed_serve",
                       "--exp", exp, "--cell", cell, "--ckpt-dir", ckpt,
                       "--chunk", str(chunk),
                       "--max-rounds", str(max_rounds), "--result", res]
                if backend:
                    cmd += ["--backend", backend]
                proc = subprocess.run(cmd, capture_output=True, text=True,
                                      timeout=900, env=env)
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"cold_start {name}/{phase} failed:\n"
                        + proc.stdout[-2000:] + proc.stderr[-2000:])
                with open(res) as f:
                    recs[phase] = json.load(f)
            cold_s = recs["cold"]["meta"]["ttfr_s"]
            warm_s = recs["warm"]["meta"]["ttfr_s"]
            warm_stats = (recs["warm"]["meta"]["progcache"]
                          or {}).get("stats", {})
            if not warm_stats.get("hit"):
                raise RuntimeError(
                    f"cold_start {name}: warm run hit nothing "
                    f"(stats {warm_stats}) — cache key unstable across "
                    "processes?")
            eq = recs["cold"]["history"] == recs["warm"]["history"]
            speedup = cold_s / warm_s
            rows.append((
                f"cold_start_{name}", warm_s * 1e6,
                f"ttfr_cold={cold_s:.3f}s;ttfr_warm={warm_s:.3f}s"
                f";speedup={speedup:.1f}x;bitwise_equal_histories={eq}",
                {"ttfr_cold_s": cold_s, "ttfr_warm_s": warm_s,
                 "speedup": speedup, "bitwise_equal_histories": eq,
                 "rounds": max_rounds, "chunk": chunk,
                 "backend": backend or "cohort",
                 "progcache_warm_stats": warm_stats}))
        finally:
            shutil.rmtree(work, ignore_errors=True)
    return rows


# ---------------- kernel micro-benches --------------------------------------
@bench("kernel_matmul")
def kmatmul():
    from repro.kernels import ops
    a = jnp.ones((512, 512), jnp.float32)
    b = jnp.ones((512, 512), jnp.float32)
    us = _timeit(lambda: jax.block_until_ready(ops.matmul(a, b)))
    fl = 2 * 512**3
    return [("kernel_matmul_512", us, f"GFLOPs={fl/us/1e3:.2f}(interp)")]


@bench("kernel_flash_attention")
def kflash():
    from repro.kernels.flash_attention import flash_attention
    q = jnp.ones((4, 512, 64), jnp.float32)
    us = _timeit(lambda: jax.block_until_ready(
        flash_attention(q, q, q, causal=True, bq=128, bk=128)))
    return [("kernel_flash_512", us, "interp")]


@bench("kernel_ssd")
def kssd():
    from repro.kernels import ops
    x = jnp.ones((8, 256, 64), jnp.float32)
    dt = jnp.full((8, 256), 0.1, jnp.float32)
    A = jnp.full((8,), -1.0, jnp.float32)
    Bm = jnp.ones((8, 256, 16), jnp.float32)
    us = _timeit(lambda: jax.block_until_ready(ops.ssd(x, dt, A, Bm, Bm, chunk=64)))
    return [("kernel_ssd_256", us, "interp")]


@bench("kernel_topk")
def ktopk():
    from repro.kernels import ops
    x = jnp.asarray(np.random.default_rng(0).standard_normal((256, 256)), jnp.float32)
    us = _timeit(lambda: jax.block_until_ready(ops.topk_compress(x, 512)[0]))
    out, kept = ops.topk_compress(x, 512)
    return [("kernel_topk_256x256", us, f"kept={int(kept)}/target512")]


@bench("kernel_basis_project")
def kbasis():
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    V = jnp.asarray(np.linalg.qr(rng.standard_normal((512, 64)))[0], jnp.float32)
    A = jnp.asarray(rng.standard_normal((512, 512)), jnp.float32)
    us = _timeit(lambda: jax.block_until_ready(ops.basis_project(V, A)))
    return [("kernel_basis_project_512", us, "interp")]


def _write_json(json_dir, group, rows):
    from repro.core.progcache import env_fingerprint

    record = {
        "bench": group,
        "unix_time": time.time(),
        "environment": env_fingerprint(),
        "rows": [
            {"name": row[0], "us_per_call": row[1], "derived": row[2],
             **({"extra": row[3]} if len(row) > 3 else {})}
            for row in rows
        ],
    }
    os.makedirs(json_dir, exist_ok=True)
    path = os.path.join(json_dir, f"BENCH_{group}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", action="store_true",
                    help="write a BENCH_<name>.json record per bench group")
    ap.add_argument("--json-dir", default=".",
                    help="directory for --json records (default: cwd)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and args.only not in name:
            continue
        try:
            rows = fn()
            for row in rows:
                print(f"{row[0]},{row[1]:.1f},{row[2]}", flush=True)
            if args.json:
                _write_json(args.json_dir, name, rows)
        except Exception as e:  # keep the harness robust
            print(f"{name},ERROR,{type(e).__name__}:{e}", flush=True)


if __name__ == "__main__":
    main()
