"""FLOPs per round (bench/flops) against hand counts at a tiny shape."""
import importlib.util
import os

import pytest

import benchpath

CELL_BL2 = {"params": {"tau": 2}}


def _flops(name):
    path = os.path.join(benchpath.BENCH, "flops", name + ".py")
    spec = importlib.util.spec_from_file_location(f"flops_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bl1_hand_count():
    bl1 = _flops("bl1")
    prob = {"n_clients": 2, "m": 3, "d": 4, "r": 2}
    t = bl1.terms(prob, {})
    # n(2mdr + 2mr² + mr), 4nmd, n(2r²d + 2rd²), 9d³, 2d³ + 10d²
    assert t == {"coeff_target": 156, "gradients": 96, "shift_reconstruction": 192,
                 "eigh": 576, "proj_mu_refine": 288}
    assert bl1.per_round(prob, {}) == 1308.0


def test_bl1_at_fig1_xl():
    bl1 = _flops("bl1")
    t = bl1.terms({"n_clients": 512, "m": 32, "d": 1200, "r": 32}, {})
    assert t["shift_reconstruction"] == pytest.approx(2 * 512 * 32 * 1200 ** 2, rel=0.03)
    assert t["shift_reconstruction"] == pytest.approx(4.7e10, rel=0.05)
    assert t["eigh"] == pytest.approx(1.6e10, rel=0.03)


def test_bl2_hand_count():
    bl2 = _flops("bl2")
    prob = {"n_clients": 5, "m": 3, "d": 4, "r": 4}
    t = bl2.terms(prob, CELL_BL2)
    # d³/3 + 2d², 2τmd², 4τmd, τ(6d² + 4d)
    assert t == pytest.approx({"server_solve": 64 / 3 + 32, "hessians": 192,
                               "gradients": 96, "client_state": 224})
    assert bl2.per_round(prob, CELL_BL2) == pytest.approx(64 / 3 + 32 + 192 + 96 + 224)
