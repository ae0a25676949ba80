"""The comparison that decides ``correct``, at a size a test run holds:
the control (the reference in float32 in the program's place) and each
fault a one-chip training cell can have, planted in the program under a
whole benchmark run on the CPU, come out not correct; so do faults in the
cohort engine's scatter and fleet totals; a sound run comes out correct."""
import copy

import numpy as np
import pytest

import benchpath  # noqa: F401
import correctness
import run

# sizes at which the cells' checks behave as at full size: BL1 near its
# optimum by round 3; BL2 through the rejection-sampled cohort path
TINY = {
    "fig1-xl": ({"n_clients": 64, "m": 32, "d": 200, "r": 32}, {"k": 32 * 32}, {}),
    "fig1-xxl": ({"n_clients": 1024, "m": 8, "d": 8, "r": 8}, {"k": 16},
                 {"tau": 128, "cohort": 16, "rounds_per_cohort": 2}),
}
# a fleet small enough that clients come back in later cohorts and take part
# again, so what a cohort's scatter wrote is read back
REVISITED = ({"n_clients": 256, "m": 8, "d": 8, "r": 8}, {"k": 16},
             {"tau": 64, "cohort": 32, "rounds_per_cohort": 2})
WORKLOADS = ["xl-bl1.steady", "xxl-bl2.stream", "xl-bl1.restart"]
#: run length (s) against the cell's 45: three restarts, so that the check
#: crosses them; one chunk elsewhere
SECONDS = {"xl-bl1.restart": 12.0}


def _cell(workload, sizes=None):
    cell = copy.deepcopy(run.load_cell(workload))
    cfg = cell["config"]
    problem, comp, params = sizes or TINY[cfg["name"]]
    cfg["name"] = "tiny-" + cfg["name"]
    cfg["problem"].update(problem)
    cfg["cell"]["hess_comp"].update(comp)
    cfg["cell"]["params"].update(params)
    return cell


@pytest.mark.parametrize("workload", ["xl-bl1.steady", "xxl-bl2.stream"])
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 9])
def test_control_in_float32_is_not_correct(workload, seed):
    cfg = _cell(workload)["config"]
    ref = run._module("reference", cfg["reference"])
    sound = correctness.reference_numbers(ref, seed, cfg)
    assert correctness.judge(sound, cfg["limits"])[0]
    control = correctness.reference_numbers(ref, seed, cfg, dtype=np.float32)
    ok, checks = correctness.judge(control, cfg["limits"])
    assert not ok, checks


def _frozen_step(monkeypatch, method):
    from repro.core import specs

    cls = {"bl1": specs.BL1Spec, "bl2": specs.BL2Spec}[method]
    step = cls.step

    def frozen(self, R, env, carry, rc):
        return carry, step(self, R, env, carry, rc)[1]

    monkeypatch.setattr(cls, "step", frozen)


def _half_batch(monkeypatch, method):
    """The fleet reductions see the first half of the clients (BL1) or of
    the resident cohort (BL2); the mean is taken over those."""
    import jax.numpy as jnp

    from repro.core import rounds

    half = lambda x: x[: x.shape[0] // 2]
    if method == "bl2":
        monkeypatch.setattr(rounds.VmapReducer, "sum",
                            lambda self, x: jnp.sum(half(x), axis=0))
        return
    monkeypatch.setattr(rounds.VmapReducer, "mean",
                        lambda self, x: jnp.mean(half(x), axis=0))

    def outer_mean(self, V):
        h = V.shape[0] // 2
        return lambda W: jnp.einsum("nrd,nre->de", W[:h], V[:h]) / h

    monkeypatch.setattr(rounds.VmapReducer, "outer_mean", outer_mean)


def _altered_answer(monkeypatch, method):
    """The server's solve returns its answer scaled by 1.001."""
    from repro.core import glm, specs

    if method == "bl2":
        solve = glm.spd_solve
        monkeypatch.setattr(glm, "spd_solve", lambda *a: 1.001 * solve(*a))
        return
    solve = specs.proj_mu_solve
    monkeypatch.setattr(specs, "proj_mu_solve", lambda *a: 1.001 * solve(*a))


FAULTS = {"frozen_step": _frozen_step, "half_batch": _half_batch,
          "altered_answer": _altered_answer}


def _execute(cell, tmp_path, seed, seconds=1.0):
    import jax

    from repro.core import progcache, rounds

    jax.clear_caches()
    rounds.clear_aot_memo()
    progcache.deactivate()
    return run.execute(cell, seed, seconds, False, cache_dir=str(tmp_path), log=lambda *a: None)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload, tmp_path):
    result, checks = _execute(_cell(workload), tmp_path, 2 ** 31 + 5, SECONDS.get(workload, 1.0))
    assert result["correct"], checks
    assert list(result)[-1] == "checks"
    assert result["failed"] == 0 and result["attempted"] >= 3


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, workload, tmp_path, monkeypatch):
    cell = _cell(workload)
    FAULTS[fault](monkeypatch, cell["config"]["cell"]["method"])
    result, checks = _execute(cell, tmp_path, 2 ** 31 + 5, SECONDS.get(workload, 1.0))
    assert not result["correct"], checks


def test_resume_that_drops_the_carry_is_not_correct(tmp_path, monkeypatch):
    """A restart that resumes the checkpoint's round count and history but
    starts from a fresh carry (the checkpointed state not put back)."""
    from repro.launch import fed_serve

    monkeypatch.setattr(fed_serve, "_restore_carry", lambda ck, template: template)
    result, checks = _execute(_cell("xl-bl1.restart"), tmp_path, 2 ** 31 + 5,
                              SECONDS["xl-bl1.restart"])
    assert not result["correct"], checks


def _full_matrix_numbers(gaps, legs, z, ref_gaps, zr, ref_legs, f_star, rounds):
    """The numbers as taken from whole (rounds + 1, d) trajectories."""
    t = slice(1, rounds + 1)
    floor = correctness.GAP_FLOOR * abs(f_star)
    gap_rel = float(np.max(np.abs(gaps[t] - ref_gaps[t]) / np.maximum(np.abs(ref_gaps[t]), floor)))
    norm_gap = lambda k: float(abs(np.linalg.norm(z[k] - z[0]) - np.linalg.norm(zr[k] - zr[0]))
                               / np.linalg.norm(zr[k] - zr[0]))
    want = ref_legs(np.arange(len(gaps)))
    bits = max(float(np.max(np.abs(legs[leg] - want[leg]))) for leg in want)
    return {"gap_rel": gap_rel, "step_rel": norm_gap(1), "change_rel": norm_gap(rounds),
            "bits": bits}


def test_numbers_on_rounds_0_1_T_match_the_whole_trajectory():
    cfg = _cell("xl-bl1.steady", ({"n_clients": 16, "m": 8, "d": 48, "r": 8}, {"k": 64}, {}))
    cfg = cfg["config"]
    ref = run._module("reference", cfg["reference"])
    rounds, seed = 5, 2 ** 31 + 3
    problem, cell = cfg["problem"], cfg["cell"]
    # each run's last iterate is z_t of the same trajectory: the rounds draw nothing
    sound = [ref.run(seed, problem, cell, t) for t in range(1, rounds + 1)]
    f32 = [ref.run(seed, problem, cell, t, dtype=np.float32) for t in range(1, rounds + 1)]
    whole = lambda runs: np.stack([runs[0]["iterates"][0]] + [r["iterates"][t + 1]
                                                              for t, r in enumerate(runs)])
    against, control = sound[-1], f32[-1]
    legs = control["legs"](np.arange(rounds + 1))
    got = correctness.numbers(control["gaps"], legs, control["iterates"], against, rounds)
    want = _full_matrix_numbers(control["gaps"], legs, whole(f32), against["gaps"], whole(sound),
                                against["legs"], against["f_star"], rounds)
    assert got == want
    assert got["step_rel"] > 0 and got["change_rel"] > 0


def _scatter_dropped(monkeypatch):
    """A cohort's scatter loses its clients' new state: the store keeps the
    rows it held before the epoch (the fleet totals take the new ones)."""
    from repro.core import cohort

    unload = cohort.CohortEngine._unload_current

    def dropped(self):
        cur = self._cur
        old = {} if cur is None else {
            name: self.store.state[name][cur["idx"]].copy()
            for name, cl in zip(self._names, self._is_client) if cl}
        unload(self)
        for name, rows in old.items():
            self.store.state[name][cur["idx"]] = rows

    monkeypatch.setattr(cohort.CohortEngine, "_unload_current", dropped)


def _totals_stale(monkeypatch):
    """A cohort's scatter leaves the frozen fleet totals as they were."""
    from repro.core import cohort

    unload = cohort.CohortEngine._unload_current

    def stale(self):
        totals = {k: v.copy() for k, v in self._totals.items()}
        unload(self)
        self._totals.update(totals)

    monkeypatch.setattr(cohort.CohortEngine, "_unload_current", stale)


COHORT_FAULTS = {"scatter_dropped": _scatter_dropped, "totals_stale": _totals_stale}


@pytest.mark.parametrize("fault", sorted(COHORT_FAULTS))
def test_cohort_fault_is_not_correct(fault, tmp_path, monkeypatch):
    cell = _cell("xxl-bl2.stream", REVISITED)
    sound, checks = _execute(cell, tmp_path / "sound", 2 ** 31 + 7)
    assert sound["correct"], checks
    COHORT_FAULTS[fault](monkeypatch)
    result, checks = _execute(cell, tmp_path / "fault", 2 ** 31 + 7)
    assert not result["correct"], checks
