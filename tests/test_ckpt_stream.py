"""The checkpoint payload is written once and hashed as it leaves
(`repro.exp.artifacts.save_checkpoint`).

The manifest's digest must still be the sha256 of the ``.npz`` file's
bytes, the file must still be what `np.load` reads, a checkpoint written
the old way (`np.savez` to a seekable file, then a read-back digest) must
still load, a failed write must leave no manifest and no thread behind, and
the cohort serve loop must hand the write its host plane without a copy.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.exp import artifacts

_RNG = np.random.default_rng(17)


def _stats():
    return {"count": 0, "bytes": 0, "hash_wait_s": 0.0}


def _save(ckpt_dir, t, carry, host=None, stats=None, **kw):
    return artifacts.save_checkpoint(
        str(ckpt_dir), t=t, carry_leaves=carry,
        streams={"eval_x": np.arange(2.0 * t).reshape(t, 2),
                 "events": np.zeros(t, np.int32)},
        root_key=np.array([0, t], np.uint32), config_digest="cfg",
        host_state=host, stats=stats, **kw)


def _assert_bitwise(got, want):
    want = np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == np.ascontiguousarray(want).tobytes()


_PAYLOADS = {
    "mixed_dtypes": (
        [_RNG.normal(size=(3, 4)), np.arange(6, dtype=np.int32),
         np.array([1, 255], np.uint8), np.array([True, False]),
         (_RNG.normal(size=3) + 1j).astype(np.complex64),
         np.linspace(0, 1, 5, dtype=np.float16)],
        {"store/s": _RNG.normal(size=(8, 3, 3)),
         "totals/s": np.arange(9, dtype=np.int64)}),
    "non_contiguous": (
        [_RNG.normal(size=(20, 12))[::3, 1::2],
         np.asfortranarray(_RNG.normal(size=(7, 5))),
         np.arange(30.0)[::-2]],
        {"store/s": _RNG.normal(size=(6, 4, 4)).transpose(2, 0, 1),
         "frozen/s": _RNG.normal(size=(9, 9))[:, 3]}),
    "zero_d": (
        [np.float64(2.5), np.array(7, np.int32), np.array(-1.0, np.float32)],
        {"totals/s": np.array(3.25)}),
    "empty": (
        [np.zeros((0,)), np.zeros((3, 0, 2), np.float32)],
        {"store/s": np.zeros((0, 4))}),
    "jax_leaves": (
        [jnp.arange(12.0).reshape(3, 4), jnp.ones(5, jnp.int32)],
        None),
    # several of numpy's 16 MiB write chunks pass through the hashing queue
    "multi_chunk": (
        [_RNG.normal(size=(2_600_000,))],
        {"store/s": np.asfortranarray(_RNG.normal(size=(1300, 2000)))}),
}


@pytest.mark.parametrize("name", list(_PAYLOADS))
def test_stream_digest_is_the_file_sha256_and_loads_bitwise(tmp_path, name):
    carry, host = _PAYLOADS[name]
    stats = _stats()
    threads = threading.active_count()
    manifest = artifacts.load_json(_save(tmp_path, 3, carry, host, stats))
    npz = tmp_path / "ckpt-00000003.npz"
    assert manifest["payload_sha256"] == artifacts._sha256_file(str(npz))
    assert stats["count"] == 1 and stats["bytes"] == os.path.getsize(npz)
    assert threading.active_count() == threads
    ck = artifacts.load_checkpoint(str(tmp_path), config_digest="cfg")
    assert ck is not None and ck["t"] == 3
    for got, want in zip(ck["carry_leaves"], carry, strict=True):
        _assert_bitwise(got, want)
    assert set(ck["host_state"]) == set(host or {})
    for key, want in (host or {}).items():
        _assert_bitwise(ck["host_state"][key], want)
    _assert_bitwise(ck["root_key"], np.array([0, 3], np.uint32))


@pytest.mark.parametrize("host", [None, {"store/s": np.ones((5, 2, 2))}],
                         ids=["stacked", "host_plane"])
def test_checkpoint_written_the_old_way_still_loads(tmp_path, host):
    """A payload `np.savez` wrote to a seekable file (local headers patched
    in place, no data descriptors), its digest read back from disk."""
    carry = [np.arange(4.0), np.eye(3, dtype=np.float32)]
    streams = {"eval_x": np.zeros((2, 3))}
    base = tmp_path / "ckpt-00000002"
    payload = {f"carry/{i}": leaf for i, leaf in enumerate(carry)}
    payload.update({f"stream/{k}": v for k, v in streams.items()})
    payload.update({f"host/{k}": v for k, v in (host or {}).items()})
    payload["root_key"] = np.zeros(2, np.uint32)
    with open(str(base) + ".npz", "wb") as f:
        np.savez(f, **payload)
    manifest = {
        "schema": artifacts.CKPT_SCHEMA, "config_digest": "cfg", "t": 2,
        "n_carry_leaves": len(carry),
        "carry_leaves": [{"shape": list(x.shape), "dtype": str(x.dtype)}
                         for x in carry],
        "streams": sorted(streams), "host_state": sorted(host or {}),
        "payload_sha256": artifacts._sha256_file(str(base) + ".npz")}
    (tmp_path / "ckpt-00000002.json").write_text(json.dumps(manifest))
    ck = artifacts.load_checkpoint(str(tmp_path), config_digest="cfg")
    assert ck is not None and ck["t"] == 2
    for got, want in zip(ck["carry_leaves"], carry, strict=True):
        _assert_bitwise(got, want)
    for key, want in (host or {}).items():
        _assert_bitwise(ck["host_state"][key], want)


def test_tee_copies_a_mutable_chunk_before_queueing_it(tmp_path):
    buf = bytearray(b"a" * 4096)
    with open(tmp_path / "f", "wb") as f, artifacts._Sha256Tee(f) as out:
        out.write(buf)
        buf[:] = b"b" * 4096                # the caller reuses its buffer
        out.write(memoryview(b"tail"))
        digest = out.hexdigest()
    assert digest == hashlib.sha256(b"a" * 4096 + b"tail").hexdigest()
    assert digest == artifacts._sha256_file(str(tmp_path / "f"))
    assert out.nbytes == 4100


class _LostLeaf:
    """A payload leaf that cannot become an array."""

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("leaf lost")


class _Unpicklable:
    def __reduce__(self):
        raise RuntimeError("member write failed")


def _fail_fsync(fd):
    raise OSError("fsync failed")


@pytest.mark.parametrize("where", ["carry_leaf", "host_leaf", "member_write",
                                   "fsync"])
def test_failed_write_leaves_no_manifest_nor_thread(tmp_path, monkeypatch,
                                                    where):
    """The write raises partway through, after a large array has gone
    through the hashing queue: no manifest appears, the loader falls back
    to the previous checkpoint, and the hashing thread is joined."""
    good = [np.arange(5.0)]
    _save(tmp_path, 1, good)
    big = _RNG.normal(size=(3_000_000,))
    carry, host = [big, np.ones(3)], {"store/s": big}
    if where == "carry_leaf":
        carry.append(_LostLeaf())
    elif where == "host_leaf":
        host["totals/s"] = _LostLeaf()
    elif where == "member_write":
        carry.append(np.array([_Unpicklable()], dtype=object))
    else:
        monkeypatch.setattr(artifacts.os, "fsync", _fail_fsync)
    threads = threading.active_count()
    with pytest.raises((RuntimeError, OSError)):
        _save(tmp_path, 2, carry, host)
    monkeypatch.undo()
    assert threading.active_count() == threads
    assert not (tmp_path / "ckpt-00000002.json").exists()
    assert not (tmp_path / "ckpt-00000002.npz").exists()
    ck = artifacts.load_checkpoint(str(tmp_path), config_digest="cfg")
    assert ck is not None and ck["t"] == 1
    _assert_bitwise(ck["carry_leaves"][0], good[0])


# --------------------------------------------------------------------------
# the serve loops: what the payload step hands the write, and meta.ckpt
# --------------------------------------------------------------------------
def test_checkpoint_payload_copies_and_checkpoint_view_borrows():
    """The engine's two payloads: `checkpoint_payload` survives the run
    going on past it, `checkpoint_view` is the engine's own host plane."""
    from repro.core import client_batch, cohort, compressors, specs

    d, bb = 6, cohort.standard_basisb(6, 64)
    spec = specs.BL2Spec(
        hess_comp=compressors.TopK(k=2 * d), model_comp=compressors.Identity(),
        alpha=1.0, eta=1.0, p=1.0, tau=16, init_exact=True,
        init_hess_bits=bb.init_coeff_bits_mean(True),
        basis_bits=bb.transmission_bits_mean(), block=False)
    eng = cohort.CohortEngine(
        spec, client_batch.synthetic_store(11, 64, 8, d),
        jnp.zeros(d, jnp.float64), cohort=16, rounds_per_cohort=2,
        root_key=jax.random.PRNGKey(7), basis="standard", prefetch=False)
    eng.run_chunk(0, 3)
    _, copied = eng.checkpoint_payload()
    _, view = eng.checkpoint_view()
    assert copied.keys() == view.keys()
    stores = [k for k in view if k.startswith("store/")]
    assert stores
    for key in stores:
        live = eng.store.state[key[len("store/"):]]
        assert np.shares_memory(view[key], live)
        assert not np.shares_memory(copied[key], live)
        _assert_bitwise(copied[key], view[key])
    held = {k: v.copy() for k, v in copied.items()}
    eng.run_chunk(3, 5)                     # past an epoch unload
    assert any(not np.array_equal(view[k], held[k]) for k in stores)
    for key, value in held.items():
        _assert_bitwise(copied[key], value)
    eng.close()


def _serve(tmp_path, exp, cell, max_rounds, chunk):
    from repro.launch import fed_serve

    return fed_serve.serve(exp_name=exp, cell_name=cell, seed=2, chunk=chunk,
                           max_rounds=max_rounds, ckpt_dir=str(tmp_path),
                           no_progcache=True, log=lambda *a: None)


def _npz_bytes(ckpt_dir) -> int:
    return sum(os.path.getsize(os.path.join(ckpt_dir, f))
               for f in os.listdir(ckpt_dir) if f.endswith(".npz"))


def test_cohort_serve_writes_its_host_plane_without_a_copy(tmp_path,
                                                           monkeypatch):
    from repro.core import cohort

    engines, shared = [], []
    view = cohort.CohortEngine.checkpoint_view
    save = artifacts.save_checkpoint

    def spy_view(self):
        engines.append(self)
        return view(self)

    def spy_save(ckpt_dir, **kw):
        store = engines[-1].store.state
        shared.append({k: np.shares_memory(v, store[k[len("store/"):]])
                       for k, v in kw["host_state"].items()
                       if k.startswith("store/")})
        return save(ckpt_dir, **kw)

    monkeypatch.setattr(cohort.CohortEngine, "checkpoint_view", spy_view)
    monkeypatch.setattr(artifacts, "save_checkpoint", spy_save)
    rec = _serve(tmp_path, "cohort-smoke", "BL2", 6, 3)
    assert len(shared) == 2 and all(s and all(s.values()) for s in shared)
    ckpt = rec["meta"]["ckpt"]
    assert ckpt["count"] == 2 and ckpt["copied_bytes"] == 0
    assert ckpt["bytes"] == _npz_bytes(tmp_path)
    assert ckpt["hash_wait_s"] == pytest.approx(
        rec["meta"]["spans"]["ckpt.digest"]["total_s"])


def test_stacked_serve_counts_its_checkpoints(tmp_path):
    rec = _serve(tmp_path, "fig4", "BL2_tau_half", 4, 2)
    ckpt = rec["meta"]["ckpt"]
    assert ckpt["count"] == 2 and ckpt["copied_bytes"] == 0
    assert ckpt["bytes"] == _npz_bytes(tmp_path)
    assert ckpt["hash_wait_s"] == pytest.approx(
        rec["meta"]["spans"]["ckpt.digest"]["total_s"])
