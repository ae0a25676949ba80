"""The frozen-trunk BL-DNN cell (``granite-bldnn.steady``) at a size a test
run holds: its job adapter, plain reference and FLOP count, and the
comparison that decides ``correct`` — a sound run is correct; a planted
half-fleet aggregation, a step that leaves the weights unchanged (from
round 1, or after it) and a resume that drops the carry are not; nor is the
reference with BL-DNN's state in bfloat16 in the program's place."""
import copy

import numpy as np
import pytest

import benchpath  # noqa: F401
import correctness
import run

WORKLOAD = "granite-bldnn.steady"
#: widths cut for the CPU; depth one period, 2 of 8 experts held
TINY = [["d_model", 64], ["n_heads", 4], ["n_kv_heads", 2], ["head_dim", 16],
        ["attention_multiplier", 0.0625], ["moe.n_experts", 8], ["moe.top_k", 3],
        ["moe.d_expert", 32], ["moe.d_shared", 48], ["ssm.d_state", 8],
        ["ssm.head_dim", 16], ["ssm.chunk", 16]]
SEED = 2 ** 31 + 5


def _cell(traffic=None):
    cell = copy.deepcopy(run.load_cell(WORKLOAD))
    cfg = cell["config"]
    cfg["name"] = "tiny-" + cfg["name"]
    cfg["problem"].update(n_layers=10, n_held=2, first_held=2, vocab=256, n_clients=4,
                          seq=64, overrides=TINY)
    if traffic is not None:
        cell["traffic"] = traffic
    return cell


def _execute(cell, tmp_path, seconds=1.0):
    import jax

    from repro.core import progcache, rounds

    jax.clear_caches()
    rounds.clear_aot_memo()
    progcache.deactivate()
    return run.execute(cell, SEED, seconds, False, cache_dir=str(tmp_path),
                       log=lambda *a: None)


def test_cell_entries():
    cell = run.load_cell(WORKLOAD)
    assert [m["name"] for m in cell["end_to_end"]] == ["round_ms", "setup_s"]
    assert {m["name"] for m in cell["per_layer"]} == {
        "mamba_ms.granite", "moe_ms.granite", "oracle_mfu.granite", "oracle_ms",
        "basis_ms", "compress_ms", "server_ms", "idle_share", "round_mfu", "ckpt_ms"}
    cfg = cell["config"]
    assert cfg["job"] == "bldnn" and cfg["reference"] == "granite_bldnn"
    assert (cfg["hidden_size"], cfg["num_experts_per_tok"], cfg["intermediate_size"],
            cfg["mamba_d_state"], cfg["shared_intermediate_size"]) == (4096, 10, 768, 128, 1536)
    assert cfg["layer_types"].index("attention") == 5 and len(cfg["layer_types"]) == 10
    # the set-up's checkpoint is compared: the last round whose loss a traced
    # run (one window chunk) has served past
    traffic = cell["traffic"]
    assert cfg["compare_rounds"] == traffic["chunk"] * traffic["warm_chunks"]


def test_sound_run_is_correct(tmp_path):
    result, checks = _execute(_cell(), tmp_path)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] >= 4


def _half_fleet(monkeypatch):
    """The server averages the first half of the clients only."""
    import jax
    import jax.numpy as jnp

    from repro.core import rounds

    reduce_tree = rounds.VmapReducer.reduce_tree
    half = lambda x: x[: x.shape[0] // 2]

    def halved(self, tree, ops="mean"):
        out = reduce_tree(self, tree, ops)
        if "coeff" in tree:
            out["coeff"] = jax.tree.map(lambda x: jnp.mean(half(x), axis=0), tree["coeff"])
        return out

    monkeypatch.setattr(rounds.VmapReducer, "reduce_tree", halved)


def _frozen_step(monkeypatch, after: int = 0):
    """The server's step leaves the weights unchanged from round ``after``
    on (the rounds before it update them)."""
    import jax
    import jax.numpy as jnp

    from repro.core import specs

    step = specs.BLDNNSpec.step

    def frozen(self, R, env, carry, rc):
        new, ys = step(self, R, env, carry, rc)
        params = jax.tree.map(lambda a, b: jnp.where(rc.t < after, a, b), new[0], carry[0])
        return (params,) + new[1:], ys

    monkeypatch.setattr(specs.BLDNNSpec, "step", frozen)


def _frozen_after_round_1(monkeypatch):
    """Only the first update is applied: every later step leaves the
    weights where round 1 put them."""
    _frozen_step(monkeypatch, after=1)


FAULTS = {"half_fleet": _half_fleet, "frozen_step": _frozen_step,
          "frozen_after_round_1": _frozen_after_round_1}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, tmp_path, monkeypatch):
    FAULTS[fault](monkeypatch)
    result, checks = _execute(_cell(), tmp_path)
    assert not result["correct"], checks


def test_resume_that_drops_the_carry_is_not_correct(tmp_path, monkeypatch):
    """Restarted after every round, each resume taking the checkpoint's
    round count and history but a fresh carry: the weights of the
    checkpoint the check reads (round 4 under this traffic) are one round
    from the initial ones."""
    from repro.launch import fed_serve

    traffic = {"kind": "restart", "chunk": 1, "setup_rounds": 2, "window_restarts": 45}
    cell = _cell(traffic)
    cell["config"]["compare_rounds"] = 4
    sound, checks = _execute(cell, tmp_path / "sound", seconds=2.0)
    assert sound["correct"], checks
    monkeypatch.setattr(fed_serve, "_restore_carry", lambda ck, template: template)
    result, checks = _execute(cell, tmp_path / "dropped", seconds=2.0)
    assert not result["correct"], checks


def test_state_in_bfloat16_is_not_correct():
    cfg = _cell()["config"]
    ref = run._module("reference", cfg["reference"])
    import jax.numpy as jnp

    sound = correctness.reference_numbers(ref, SEED, cfg)
    assert correctness.judge(sound, cfg["limits"])[0]
    control = correctness.reference_numbers(ref, SEED, cfg, dtype=jnp.bfloat16)
    ok, checks = correctness.judge(control, cfg["limits"])
    assert not ok, checks


def test_reference_legs_and_iterates():
    cfg = _cell()["config"]
    ref = run._module("reference", cfg["reference"])
    out = ref.run(SEED, cfg["problem"], cfg["cell"], 2)
    assert sorted(out["iterates"]) == [0, 1, 2] and len(out["gaps"]) == 3
    numel = 64 * 64 * 2 + 64 * 32 * 2
    assert out["iterates"][0].shape == (numel,)
    legs = out["legs"](np.arange(3))
    k = sum(max(1, int(0.05 * s)) for s in (64 * 64, 64 * 32, 64 * 32, 64 * 64))
    np.testing.assert_array_equal(legs["grad_up"], np.arange(3) * k * 64.0)
    assert legs["basis_ship"][0] == (2 * 64 * 64 + 2 * (64 * 64 + 32 * 32) + 2 * 64 * 64) * 32.0
    assert out["f_star"] == 0.0


def test_flops_count_the_expert_pairs():
    flops = run._module("flops", "bldnn")
    problem = run.load_cell(WORKLOAD)["config"]["problem"]
    assert flops.per_round(problem, {}, [{"meta": {}}]) is None
    n = problem["n_clients"]
    pairs = n * 4096 * 10 * 10 * 9 / 72      # silos × tokens × layers × picks × share
    rec = {"meta": {"moe_assignments_held": pairs}, "config": {"problem": problem}}
    total = flops.per_round(problem, {}, [rec])
    t = flops.terms(problem, pairs)
    assert total == pytest.approx(sum(t.values()))
    # the forward pass: n silos × 4,096 tokens at about 1.32e9 active
    # parameters a token (the held experts at 1.25 pairs a token)
    assert 2 * 1.2e9 < t["oracle_forward"] / (n * 4096) < 2 * 1.45e9
    # rotations, Uᵀ g V of wq and wo (4096²) and of wk and wv (4096 × 1024):
    # 636 GFLOP per client, n + 1 times (the silos and the mean)
    per_client = 2 * 4 * 4096 ** 3 + 2 * (2 * 4096 ** 2 * 1024 + 2 * 4096 * 1024 ** 2)
    assert t["basis"] == (n + 1) * per_client
    assert flops.oracle_per_round([rec]) == t["oracle_forward"] + t["oracle_backward"]
    more = flops.terms(problem, 2 * pairs)
    assert more["oracle_forward"] > t["oracle_forward"]


def test_adapter_iterates_from_checkpoints(tmp_path):
    """z_0 is the problem's, z_T (under the key 1 too) the weights of the
    checkpoint of round T."""
    import system

    from repro.core import progcache

    cfg = _cell()["config"]
    progcache.deactivate()
    served = system.Served(cfg, SEED, ckpt_dir=str(tmp_path / "ck"),
                           progcache_dir=str(tmp_path / "pc"),
                           job=run._module("jobs", "bldnn"), log=None)
    served.build()
    served.serve(max_rounds=8, chunk=4)
    it = served.iterates(8)
    ref = run._module("reference", cfg["reference"]).run(SEED, cfg["problem"], cfg["cell"], 8)
    np.testing.assert_array_equal(it[0], ref["iterates"][0])
    assert sorted(it) == [0, 1, 8]
    np.testing.assert_array_equal(it[1], it[8])
    four = served.iterates(4)
    assert sorted(four) == [0, 1, 4] and not np.array_equal(four[4], it[8])
    ref4 = run._module("reference", cfg["reference"]).run(SEED, cfg["problem"], cfg["cell"], 4)
    for t, got, want, other in ((8, it[8], ref["iterates"][8], ref4["iterates"][4]),
                                (4, four[4], ref4["iterates"][4], ref["iterates"][8])):
        near = np.linalg.norm(got - want)   # nearest the reference's own round
        assert near < np.linalg.norm(got - other), t
        assert near < np.linalg.norm(got - ref["iterates"][0]), t
    with pytest.raises(ValueError, match="no checkpoint of round 3"):
        served.iterates(3)
    served.close()


def test_scope_of_a_backward_op():
    """A backward op's segments wrap the scope in transformations; it
    still counts under the name inside, the innermost model scope, and
    under the oracle."""
    import granite_scopes as gs

    label = ("jit(_chunk_body)/while/body/closed_call/oracle/while/body/closed_call/"
             "transpose(jvp(mamba))/jvp(mamba)/checkpoint/rematted_computation/dot_general")
    assert gs.innermost(label, gs.SUBSCOPES) == "mamba" and "oracle" in gs.segments(label)
    assert gs.innermost("jit(f)/compress/keep_mask/reduce_sum", gs.SUBSCOPES) is None
    assert gs.innermost("jit(f)/oracle/moe/mamba_like", gs.SUBSCOPES) == "moe"


def _scoped_trace(path):
    """Two 10 ms rounds of a frozen-trunk chunk: Mamba forward and its
    rematerialized backward (2 + 3 ms), an expert scatter (1 ms) under the
    oracle; a ``mamba`` segment outside it and a look-alike name (1 ms each)."""
    import trace_reduce as tr

    ps = 10 ** 9
    prefix = "jit(_chunk_body)/while/body/closed_call/"
    ops = {"fusion.1": prefix + "oracle/while/body/closed_call/jvp(mamba)/dot_general",
           "fusion.2": prefix + "oracle/while/body/closed_call/transpose(jvp(mamba))/"
                                "jvp(mamba)/checkpoint/rematted_computation/dot_general",
           "fusion.3": prefix + "oracle/while/body/closed_call/jvp(moe)/scatter-add",
           "fusion.4": prefix + "compress/mamba_like/reduce_sum",
           "fusion.5": prefix + "basis/mamba/dot_general"}
    X = tr._cls
    sp = X("XSpace")()
    meta = sp.planes.add(name="/host:metadata")
    meta.stat_metadata[1].name = "Hlo Proto"
    hp = X("HloProto")()
    comp = hp.hlo_module.computations.add(name="main")
    for ins, op in ops.items():
        comp.instructions.add(name=ins).metadata.op_name = op
    meta.event_metadata[1].stats.add(metadata_id=1, bytes_value=hp.SerializeToString())
    dev = sp.planes.add(name="/device:TPU:0")
    for i, n in enumerate(sorted(ops)):
        dev.event_metadata[i + 1].name = f"%{n} = f32[2] op(...)"
    dev.event_metadata[99].name = "Trace Buffers Dropped"
    line = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    for k in range(2):
        for i, (start, dur) in enumerate(((0, 2), (2, 3), (5, 1), (6, 1), (7, 1))):
            line.events.add(metadata_id=i + 1, offset_ps=(10 * k + start) * ps,
                            duration_ps=dur * ps)
    dev.lines.add(name="XLA TraceMe", timestamp_ns=0).events.add(
        metadata_id=99, offset_ps=1000 * ps, duration_ps=1000)
    with open(path, "wb") as f:
        f.write(sp.SerializeToString())
    return tr.load(path)


@pytest.mark.parametrize("name,value", [("mamba_ms.granite", 5.0), ("moe_ms.granite", 1.0)])
def test_oracle_scope_reader_on_a_marked_trace(name, value, tmp_path):
    import types

    trace = _scoped_trace(str(tmp_path / "t.xplane.pb"))
    view = types.SimpleNamespace(trace=trace, window=(0.0, 20e6), rounds=2)
    assert run._module("metrics", name).read(view) == pytest.approx(value)
    none = types.SimpleNamespace(trace=None, window=None, rounds=2)
    assert run._module("metrics", name).read(none) is None
