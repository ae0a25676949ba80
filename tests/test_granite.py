"""The Granite-4.0-H-Small cut and its BL-DNN fine-tuning on the CPU, at a
small size: the served model against the plain reference
(`repro.models.granite_reference`), the dropless expert layer's shares, the
per-layer SVD basis on stacked leaves, and the serve loop's resume."""
import dataclasses
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import basis as B
from repro.core import rounds
from repro.exp import registry
from repro.exp.engine import build_problem
from repro.fed import bldnn
from repro.launch import fed_serve
from repro.models import granite_reference as R
from repro.models import layers as L

jax.config.update("jax_enable_x64", True)

#: widths cut for the CPU (the depth is one period, or two for 2 groups)
TINY = (("d_model", 64), ("n_heads", 4), ("n_kv_heads", 2), ("head_dim", 16),
        ("attention_multiplier", 0.0625), ("moe.n_experts", 8), ("moe.top_k", 3),
        ("moe.d_expert", 32), ("moe.d_shared", 48), ("ssm.d_state", 8),
        ("ssm.head_dim", 16), ("ssm.chunk", 16))
SEED = 2 ** 31 + 5
CELL = {"params": {"top_k_frac": 0.05}}


def _problem(n_layers=20, n_clients=3, seq=64):
    return dict(n_layers=n_layers, n_held=2, first_held=2, vocab=256,
                n_clients=n_clients, seq=seq, zipf=1.1, overrides=TINY)


def _build(problem):
    return build_problem(registry.LMFinetuneSpec(seed=SEED, **problem))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def test_published_widths():
    cfg = get_config("granite_40_h_small")
    assert (cfg.d_model, cfg.n_layers, cfg.vocab_size, cfg.n_heads, cfg.n_kv_heads) == (
        4096, 40, 100_352, 32, 8)
    assert [s.mixer for s in cfg.layer_specs()].count("attn") == 4
    assert [i for i, s in enumerate(cfg.layer_specs()) if s.mixer == "attn"] == [5, 15, 25, 35]
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.d_expert, cfg.moe.d_shared) == (
        72, 10, 768, 1536)
    assert (cfg.ssm.d_state, cfg.ssm.head_dim, cfg.n_ssm_heads, cfg.ssm.conv_width) == (
        128, 64, 128, 4)
    assert not cfg.rope and cfg.moe.dropless and cfg.ssm.conv_bias
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attention_multiplier,
            cfg.logits_scaling) == (12.0, 0.22, 1 / 128, 16.0)


def test_weights_and_ids_are_the_reference_draw():
    problem = _problem()
    prob = _build(problem)
    trunk, fed = R.weights(SEED, R.dims(problem))
    flat = {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(prob.trunk)[0]}
    assert set(flat) == set(trunk)
    for name in trunk:
        assert flat[name].dtype == trunk[name].dtype, name
        np.testing.assert_array_equal(np.asarray(flat[name], np.float32),
                                      np.asarray(trunk[name], np.float32))
    for k in fed:
        np.testing.assert_array_equal(np.asarray(prob.params0[k]), np.asarray(fed[k]))
    np.testing.assert_array_equal(
        np.asarray(prob.batch.data["ids"]),
        R.tokens(SEED, problem["n_clients"], problem["seq"] + 1, 256, 1.1))


def test_loss_and_gradients_match_reference():
    """Two groups of the pattern, float32 on both sides: the chunked SSD
    and the blocked attention against a token-by-token recurrence and
    dense softmaxes agree to float32 rounding (tolerance 1e-4 relative:
    different summation orders over 64 tokens and 10 to 20 layers)."""
    problem = _problem()
    prob = _build(problem)
    ids = prob.batch.data["ids"][0]
    c = R.dims(problem)
    rt, rf = R.weights(SEED, c)
    with jax.default_matmul_precision("highest"):
        (loss, counts), g = jax.value_and_grad(prob.loss_fn, has_aux=True)(
            prob.params0, _f32(prob.trunk), {"ids": ids})
        ref_loss, ref_g = jax.value_and_grad(lambda w: R.client_loss(w, rt, ids, c))(rf)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    for k in rf:
        scale = float(jnp.max(jnp.abs(ref_g[k])))
        assert scale > 0
        assert float(jnp.max(jnp.abs(g[k] - ref_g[k]))) <= 1e-4 * scale, k
    assert int(counts[1]) == 0 and int(counts[0]) > 0


def test_bf16_oracle_loss_near_reference():
    """The served dtype policy (bf16 trunk and activations, f32 norms,
    softmax, SSM and loss): the loss agrees to 1e-3 relative, bf16's 8-bit
    mantissa through 20 residual updates."""
    problem = _problem()
    prob = _build(problem)
    ids = prob.batch.data["ids"][1]
    c = R.dims(problem)
    rt, rf = R.weights(SEED, c)
    loss, _ = prob.loss_fn(prob.params0, prob.trunk, {"ids": ids})
    with jax.default_matmul_precision("highest"):
        ref = R.client_loss(rf, rt, ids, c)
    assert abs(float(loss) - float(ref)) <= 1e-3 * abs(float(ref))


def test_three_rounds_match_reference():
    """Three BL-DNN rounds of the round engine (rotation, per-leaf Top-K
    shift learning, Fisher shifts, preconditioned step) with a float32
    trunk against the reference's: the steps to 1e-4 relative beyond the
    float32 rounding of the iterates themselves (the Top-K sets agree), the
    losses to 1e-5, the ledger's legs exactly."""
    problem = _problem(n_layers=10, n_clients=3)
    prob = _build(problem)
    exp = registry.Experiment(
        name="granite-3rounds", figure="extra", title="t", paper_ref="t",
        problem=prob.spec, cells=(_cell(),))
    spec, batch, basisb = bldnn.serve_setup(exp.cells[0], prob)
    x0 = (prob.params0, _f32(prob.trunk))
    carry = rounds.init_serve_carry(spec, batch, basisb, x0)
    names = sorted(prob.params0)
    got, ys = {}, []
    with jax.default_matmul_precision("highest"):
        for t0, t1 in ((0, 1), (1, 3)):      # the params lead the carry
            carry, y = rounds.run_chunk(spec, batch, basisb, x0, carry, t0, t1 - t0,
                                        jax.random.PRNGKey(0))
            got[t1] = [np.asarray(carry[0][k]) for k in names]   # before donation
            ys.append(y)
        ref = R.run(SEED, problem, CELL, 3)
        first = R.run(SEED, problem, CELL, 1)["iterates"][1]
    ys = jax.tree.map(lambda *a: np.concatenate([np.asarray(x) for x in a]), *ys)
    flat = lambda ls: np.concatenate([np.asarray(x, np.float64).ravel() for x in ls])
    for t, leaves in got.items():
        want = first if t == 1 else ref["iterates"][t]
        step = np.linalg.norm(want - ref["iterates"][0])
        # each side rounds the iterate to float32 every round
        rounding = t * 2.0 ** -24 * np.linalg.norm(want)
        assert np.linalg.norm(flat(leaves) - want) <= 1e-4 * step + 2 * rounding, t
    np.testing.assert_allclose(np.asarray(ys[0]["loss"]), ref["gaps"][:3], rtol=1e-5)
    legs = ref["legs"](np.arange(3))
    for leg, want in legs.items():
        np.testing.assert_array_equal(np.asarray(getattr(ys[1], leg)), want)


def _cell():
    return registry.MethodCell("BLDNN", "bldnn", 6, basis="per_layer_svd",
                               hess_comp=registry.CompressorCfg(kind="topk"),
                               params=(("top_k_frac", 0.05),), backend="fast")


# ---------------------------------------------------------------- experts
def _moe_cfg(**moe):
    cfg = bldnn.lm_model_config("granite_40_h_small", 10, 0, 0, 256, TINY)
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe))


def _uncut_layer(p, x, cfg):
    """Every expert on every token, weighted by the softmax over the top-k
    router logits, plus the shared expert: the layer on one chip."""
    xt = x.reshape(-1, x.shape[-1])
    top, ids = jax.lax.top_k(xt @ p["router"], cfg.moe.top_k)
    gates = jax.nn.softmax(top, axis=-1)
    E = cfg.moe.n_experts
    gate = jnp.sum(jnp.where(ids[:, :, None] == jnp.arange(E), gates[:, :, None], 0.0), 1)
    ye = jnp.stack([(jax.nn.silu(xt @ p["wg"][e]) * (xt @ p["wi"][e])) @ p["wo"][e]
                    for e in range(E)], 1)
    out = jnp.sum(gate[:, :, None] * ye, 1)
    sh = p["shared"]
    out = out + (jax.nn.silu(xt @ sh["wg"]) * (xt @ sh["wi"])) @ sh["wo"]
    return out.reshape(x.shape)


@pytest.mark.parametrize("held", [1, 2, 4])
def test_expert_shares_sum_to_the_uncut_layer(held):
    full = _moe_cfg(n_held=0)
    p = L.init_moe(jax.random.PRNGKey(3), full, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = _uncut_layer(p, x, full)
        total, assigned = jnp.zeros_like(x), 0
        for first in range(0, 8, held):
            cfg = _moe_cfg(n_held=held, first_held=first)
            share = {**p, **{k: p[k][first:first + held] for k in ("wi", "wg", "wo")}}
            out, counts = L.moe_dropless(share, x, cfg)
            total, assigned = total + out, assigned + int(counts["assigned"])
            assert int(counts["dropped"]) == 0
        sh = p["shared"]
        total = total + L.mlp(sh, x, True, None)      # every chip computes it alike
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert assigned == x.shape[0] * x.shape[1] * full.moe.top_k


def test_no_token_dropped_under_skewed_routing():
    """Every token routes to the held experts (the router favours them):
    the pairs overflow the expected-load buffer and the layer takes its
    every-pair branch; nothing is dropped and the result is the uncut
    layer's held part."""
    cfg = _moe_cfg(n_held=2, first_held=0)
    p = L.init_moe(jax.random.PRNGKey(5), _moe_cfg(n_held=0), jnp.float32)
    p["router"] = p["router"].at[:, :2].add(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (1, 512, 64), jnp.float32))
    share = {**p, **{k: p[k][:2] for k in ("wi", "wg", "wo")}}
    with jax.default_matmul_precision("highest"):
        out, counts = L.moe_dropless(share, x, cfg)
        xt = x[0]
        top, ids = jax.lax.top_k(xt @ p["router"], 3)
        gates = jax.nn.softmax(top, axis=-1)
        want = sum(jnp.sum(jnp.where(ids == e, gates, 0.0), 1)[:, None]
                   * ((jax.nn.silu(xt @ p["wg"][e]) * (xt @ p["wi"][e])) @ p["wo"][e])
                   for e in range(2))
    assert int(counts["assigned"]) == 1024           # both held experts, every token
    assert int(counts["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want), rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ basis
@pytest.mark.parametrize("shape,view", [((2, 12, 3, 4), (1, 1)), ((2, 3, 4, 12), (1, 2)),
                                        ((3, 10, 6), (1, 1)), ((10, 6, 2), (0, 1))])
def test_rotation_round_trips_on_stacked_leaves(shape, view):
    key = jax.random.PRNGKey(7)
    params = {"a": jax.random.normal(key, shape), "b": jnp.ones((5,)),
              "c": jax.random.normal(key, (6, 7))}
    views = (view, None, None)                  # leaves in order a, b, c
    basis = B.per_layer_svd_basis(params, views=views)
    slices = int(np.prod(shape[:view[0]]))
    assert basis.slices_rotated == slices + 1   # a's slices and c
    g = jax.tree.map(lambda p: jax.random.normal(jax.random.PRNGKey(8), (4,) + p.shape), params)
    back = basis.unrotate(basis.rotate(g))
    for k in params:
        np.testing.assert_allclose(np.asarray(back[k]), np.asarray(g[k]), atol=1e-5)
    rot = basis.rotate(g)["a"]
    # the rotation is orthogonal on each slice of the matrix view
    np.testing.assert_allclose(float(jnp.linalg.norm(rot)), float(jnp.linalg.norm(g["a"])),
                               rtol=1e-5)
    assert basis.ship_floats() == sum(u.size + v.size for u, v in
                                      (uv for uv in basis.UV if uv is not None))


def test_per_layer_svd_refuses_a_leaf_it_cannot_rotate():
    with pytest.raises(ValueError, match="matrix view"):
        B.per_layer_svd_basis({"w": jnp.ones((2, 3, 4))})


def test_served_basis_rotates_every_federated_slice():
    prob = _build(_problem(n_layers=20))
    spec, batch, basisb = bldnn.serve_setup(_cell(), prob)
    assert basisb.slices_rotated == 8           # 4 leaves × 2 groups
    assert spec.frozen


# ------------------------------------------------------------------ serve
def _register(name, problem):
    exp = registry.Experiment(
        name=name, figure="extra", title="t", paper_ref="t",
        problem=registry.LMFinetuneSpec(seed=SEED, **problem), cells=(_cell(),))
    if name not in registry.EXPERIMENT_REGISTRY:
        registry.register_experiment(exp)
    return exp


def _serve(tmp, name, **kw):
    return fed_serve.serve(exp_name=name, cell_name="BLDNN", seed=11,
                           ckpt_dir=str(tmp), log=lambda *a: None, **kw)


def _carry(ckpt_dir):
    from repro.exp import artifacts

    ck = artifacts.load_checkpoint(str(ckpt_dir))
    return ck["t"], ck["carry_leaves"]


def _same(a, b):
    assert a["history"] == b["history"]
    for k in ("moe_dropped", "basis_slices_rotated"):
        assert a["meta"][k] == b["meta"][k]


def test_fed_serve_bldnn_chunkings_and_kill_resume_are_bitwise(tmp_path):
    problem = _problem(n_layers=10, n_clients=2, seq=32)
    _register("granite-serve", problem)
    whole = _serve(tmp_path / "whole", "granite-serve", chunk=2, max_rounds=6)
    assert whole["meta"]["moe_dropped"] == 0.0
    assert whole["meta"]["basis_slices_rotated"] == 4
    assert whole["meta"]["moe_assignments_held"] > 0
    t_w, c_w = _carry(tmp_path / "whole")
    other = _serve(tmp_path / "other", "granite-serve", chunk=3, max_rounds=6)
    _same(whole, other)
    t_o, c_o = _carry(tmp_path / "other")
    assert t_w == t_o == 6
    for a, b in zip(c_w, c_o):
        np.testing.assert_array_equal(a, b)

    # kill -9 once the chunk of rounds 2..3 is done, before its checkpoint;
    # then resume
    killed = tmp_path / "killed"
    script = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(bldnn.__file__)))!r})
        import jax
        jax.config.update("jax_enable_x64", True)
        from repro.exp import registry
        from repro.launch import fed_serve
        exp = registry.Experiment(
            name="granite-serve", figure="extra", title="t", paper_ref="t",
            problem=registry.LMFinetuneSpec(seed={SEED}, **{problem!r}),
            cells=(registry.MethodCell("BLDNN", "bldnn", 6, basis="per_layer_svd",
                   hess_comp=registry.CompressorCfg(kind="topk"),
                   params=(("top_k_frac", 0.05),), backend="fast"),))
        registry.register_experiment(exp)
        fed_serve.serve(exp_name="granite-serve", cell_name="BLDNN", seed=11,
                        chunk=2, max_rounds=6, ckpt_dir={str(killed)!r},
                        crash_after_round=2, log=lambda *a: None)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True,
                          timeout=600)
    assert proc.returncode != 0, proc.stderr[-2000:]
    assert _carry(killed)[0] == 2               # round 3's chunk never landed
    resumed = _serve(killed, "granite-serve", chunk=2, max_rounds=6)
    assert resumed["meta"]["resumed_from"] == 2
    assert resumed["history"] == whole["history"]
    t_r, c_r = _carry(killed)
    assert t_r == 6
    for a, b in zip(c_w, c_r):
        np.testing.assert_array_equal(a, b)
    shutil.rmtree(tmp_path, ignore_errors=True)


def test_long_rows_select_as_top_k():
    """Rows of `compressors.SEARCH_ROWS_FROM` entries or more select by the
    bitwise threshold search: the same entries as XLA's ``top_k``, ties
    at the threshold broken by earliest index."""
    from repro.core import compressors
    from repro.kernels.topk_threshold import keep_mask

    T = compressors.SEARCH_ROWS_FROM
    v = jax.random.normal(jax.random.PRNGKey(9), (2, T), jnp.float32)
    v = v.at[:, 10:20].set(3.0)                     # a tie group
    for k in (1, 15, 52_428):
        a32 = jnp.abs(v)
        want = keep_mask(a32, jax.lax.top_k(a32, k)[0][:, -1:], k)
        got = compressors.topk_keep_mask(v, k)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert (np.asarray(got).sum(axis=1) == k).all()


def test_rows_past_the_groups_do_not_leak(monkeypatch):
    """A TPU's ragged_dot leaves the rows past its groups undefined: the
    expert layer's result and its input gradient must not read them."""
    cfg = _moe_cfg(n_held=2, first_held=2)
    p = L.init_moe(jax.random.PRNGKey(10), _moe_cfg(n_held=0), jnp.float32)
    share = {**p, **{k: p[k][2:4] for k in ("wi", "wg", "wo")}}
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 96, 64), jnp.float32)
    loss = lambda x: jnp.sum(L.moe_dropless(share, x, cfg)[0] ** 2)
    want, want_g = jax.value_and_grad(loss)(x)
    ragged = jax.lax.ragged_dot

    def undefined_past_groups(lhs, rhs, sizes, **kw):
        out = ragged(lhs, rhs, sizes, **kw)
        past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(jax.lax, "ragged_dot", undefined_past_groups)
    got, got_g = jax.value_and_grad(loss)(x)
    assert np.isfinite(float(got)) and np.isfinite(np.asarray(got_g)).all()
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g), rtol=1e-5, atol=1e-6)
