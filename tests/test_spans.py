"""Host spans (`repro.core.spans`), the serve records' per-phase view
(``meta.spans``, ``meta.retraces``), and the device layer scopes of the
round programs, which must change the programs' metadata and nothing else."""
import contextlib
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched, client_batch, glm, rounds, spans, specs
from repro.core.compressors import Identity, TopK

jax.config.update("jax_enable_x64", True)


# ==========================================================================
# spans.span / snapshot / reset
# ==========================================================================
def test_span_nests_and_exposes_its_duration():
    before = spans.snapshot()
    with spans.span("test.outer", k=1) as outer:
        with spans.span("test.inner") as inner:
            time.sleep(0.01)
        assert inner.elapsed_s >= 0.01 and outer.elapsed_s is None
    assert outer.elapsed_s >= inner.elapsed_s
    delta = spans.since(before)
    assert delta["test.outer"]["count"] == 1 and delta["test.inner"]["count"] == 1
    assert delta["test.outer"]["total_s"] == pytest.approx(outer.elapsed_s)
    row = spans.snapshot()["test.inner"]
    assert row["max_s"] >= inner.elapsed_s


def test_span_records_when_the_block_raises():
    before = spans.snapshot()
    with pytest.raises(ValueError):
        with spans.span("test.raises"):
            raise ValueError("boom")
    assert spans.since(before)["test.raises"]["count"] == 1


def test_snapshot_and_reset_behave_like_trace_counts():
    with spans.span("test.snap"):
        pass
    snap = spans.snapshot()
    snap["test.snap"]["count"] = -5          # a copy: the table is untouched
    assert spans.snapshot()["test.snap"]["count"] >= 1
    spans.reset()
    assert spans.snapshot() == {}
    with spans.span("test.snap"):
        pass
    assert spans.snapshot()["test.snap"]["count"] == 1
    # like rounds.trace_counts: a snapshot is a plain dict, reset clears
    assert isinstance(rounds.trace_counts(), dict)


def test_spans_are_thread_safe():
    """More threads than cores, a short switch interval: no count lost."""
    threads, per = 16, 400
    before = spans.snapshot()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                with spans.span("test.threads"):
                    pass

        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
    assert spans.since(before)["test.threads"]["count"] == threads * per


# ==========================================================================
# serve records
# ==========================================================================
_STACKED = {"serve.warm": 1, "serve.restore": 1, "serve.dispatch": 2,
            "serve.pull": 2, "serve.sink": 2, "serve.checkpoint": 2,
            "ckpt.payload": 2, "ckpt.write": 4, "ckpt.digest": 2,
            "ckpt.prune": 2}


def _serve(tmp_path, exp, cell, max_rounds, chunk=4):
    from repro.launch import fed_serve

    return fed_serve.serve(exp_name=exp, cell_name=cell, seed=1, chunk=chunk,
                           max_rounds=max_rounds, ckpt_dir=str(tmp_path / "ck"),
                           metrics_out=str(tmp_path / "m.jsonl"),
                           log=lambda *a: None)


def test_stacked_serve_record_carries_spans_and_retraces(tmp_path):
    rec = _serve(tmp_path, "fig4", "BL2_tau_half", 8)
    meta = rec["meta"]
    assert {k: v["count"] for k, v in meta["spans"].items()} == _STACKED
    assert all(v["total_s"] >= 0 for v in meta["spans"].values())
    assert all(isinstance(n, int) and n > 0 for n in meta["retraces"].values())
    # the serve loop opens a span for the whole checkpoint step
    ck = meta["spans"]
    assert ck["serve.checkpoint"]["total_s"] >= ck["ckpt.payload"]["total_s"] \
        + ck["ckpt.digest"]["total_s"]
    # extending the finished job: the programs are resolved, nothing traces
    more = _serve(tmp_path, "fig4", "BL2_tau_half", 12)
    assert more["meta"]["resumed_from"] == 8
    assert more["meta"]["retraces"] == {}
    assert more["meta"]["spans"]["serve.dispatch"]["count"] == 1


def test_cohort_serve_record_carries_spans_and_prefetch_counters(tmp_path):
    rec = _serve(tmp_path, "cohort-smoke", "BL2", 8)
    meta = rec["meta"]
    got = meta["spans"]
    for name in ("serve.warm", "serve.restore", "serve.dispatch", "serve.pull",
                 "serve.sink", "serve.checkpoint", "ckpt.payload", "ckpt.write",
                 "ckpt.digest", "ckpt.prune", "cohort.unload", "cohort.load",
                 "cohort.prefetch_wait", "cohort.gather"):
        assert got[name]["count"] >= 1, name
    assert isinstance(meta["retraces"], dict)
    pf = meta["prefetch"]
    assert pf["epochs_loaded"] == got["cohort.load"]["count"]
    # the prefetch counters are the spans' durations
    assert pf["prefetch_wait_us"] == pytest.approx(
        got["cohort.prefetch_wait"]["total_s"] * 1e6)
    assert pf["epochs_prefetched"] == got["cohort.prefetch_wait"]["count"]


# ==========================================================================
# device layer scopes
# ==========================================================================
def _problem(method):
    from repro.core.basis import make_bases

    clients = glm.make_synthetic(seed=4, n_clients=6, m=20, d=16, r=4, lam=1e-3)
    x0 = jnp.zeros(16, jnp.float64)
    if method == "bl1":   # §2.3 block mode, as fig1-xl serves it
        spec, batch, basisb = batched.bl1_setup(
            clients, make_bases("data_outer", clients), [TopK(k=16)] * 6, Identity())
        assert spec.block
    else:
        spec, batch, basisb = batched.bl2_setup(
            clients, make_bases("standard", clients), [TopK(k=32)] * 6,
            [Identity()] * 6, tau=3)
    return spec, batch, basisb, x0


#: the layer scopes, as op_name path segments
LAYER_SCOPES = ("oracle", "basis", "compress", "reduce", "server")
#: every entry point that opens a layer scope, by owner and attribute
_SCOPED = [
    (rounds.Reducer, ("reduce_tree", "once")),
    (rounds.VmapReducer, ("mean", "sum", "max")),
    (rounds.ShardMapReducer, ("mean", "sum", "max", "reduce_tree",
                              "tree_mean_presummed", "once")),
    (rounds.CohortReducer, ("once", "sum", "reduce_tree")),
    (rounds, ("shift_update", "shift_update_sum", "downlink_broadcast",
              "coeff_layout")),
    (specs, ("shift_update", "downlink_broadcast", "coeff_layout")),
    (client_batch, ("grads", "hess")),
]


class _NoScope(contextlib.ContextDecorator):
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _compiled(spec, batch, basisb, x0):
    jax.clear_caches()
    R = rounds.VmapReducer(n=batch.n)
    carry = rounds.init_serve_carry(spec, batch, basisb, x0)
    ts = jnp.arange(3, 6)
    keys = jax.vmap(lambda t: jax.random.fold_in(jax.random.PRNGKey(5), t))(ts)
    avail = jnp.ones((3, batch.n), bool)
    args = (batch, basisb, x0, carry, ts, keys, avail)
    compiled = rounds._chunk_jit_aot.lower(spec, R, *args).compile()
    return compiled, compiled(*args)


def _segments(text):
    return {seg for name in re.findall(r'op_name="([^"]*)"', text)
            for seg in name.split("/")}


def _without_metadata(text):
    """The HLO module less its metadata and its trailing table of source
    locations."""
    return re.sub(r",? metadata=\{[^}]*\}", "", text.split("\nFileNames")[0])


@pytest.mark.parametrize("method", ["bl1", "bl2"])
def test_layer_scopes_change_metadata_only(method, monkeypatch):
    """The chunk program carries the five layer scopes as op_name segments;
    with every scope taken out it compiles to the same program (metadata
    aside) and computes bitwise the same chunk."""
    problem = _problem(method)
    scoped, out = _compiled(*problem)
    assert set(LAYER_SCOPES) <= _segments(scoped.as_text())

    for owner, names in _SCOPED:
        for name in names:
            monkeypatch.setattr(owner, name, getattr(owner, name).__wrapped__)
    monkeypatch.setattr(jax, "named_scope", _NoScope)
    try:
        plain, out_plain = _compiled(*problem)
    finally:
        jax.clear_caches()      # later tests must not meet the unscoped traces
    assert not set(LAYER_SCOPES) & _segments(plain.as_text())
    assert _without_metadata(plain.as_text()) == _without_metadata(scoped.as_text())
    for a, b in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(out_plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
