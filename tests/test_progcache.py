"""Two-tier AOT program cache (`repro.core.progcache`).

The contract under test (ISSUE 10 acceptance): serve programs dispatched
through the cache produce trajectories bitwise-identical to the uncached
fast path whether the executable was freshly compiled (miss) or
deserialized from disk (hit), on both reducers; and EVERY failure mode —
corrupt payload, torn manifest, version/environment skew — falls back to a
live compile that is itself bitwise-identical, never an error and never
different bits.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched, comm, progcache, rounds
from repro.core.compressors import Identity, TopK

jax.config.update("jax_enable_x64", True)


@pytest.fixture(scope="module")
def problem():
    from repro.core import glm
    from repro.core.basis import orth_basis_from_data

    clients = glm.make_synthetic(seed=0, n_clients=6, m=24, d=18, r=6,
                                 lam=1e-3)
    bases = [orth_basis_from_data(c.A) for c in clients]
    x0 = jnp.zeros(18, jnp.float64)
    spec, batch, basisb = batched.bl2_setup(
        clients, bases, [TopK(k=6) for _ in clients],
        [Identity() for _ in clients], tau=3)
    return spec, batch, basisb, x0


@pytest.fixture
def cache_dir(tmp_path):
    """A fresh active cache per test; the global active-cache slot and the
    in-process executable memo are scrubbed on the way out so later tests
    (here and in other files) see the pre-subsystem fast path."""
    root = str(tmp_path / "progcache")
    rounds.clear_aot_memo()
    progcache.activate(root)
    yield root
    progcache.deactivate()
    rounds.clear_aot_memo()


def _serve_rounds(problem, *, sharded=False, t1=8, chunk=4):
    """Drive [0, t1) in chunks from a fresh carry; returns concrete
    (trajectory, per-leg bits, events) arrays."""
    spec, batch, basisb, x0 = problem
    root = jax.random.PRNGKey(7)
    carry = rounds.init_serve_carry(spec, batch, basisb, x0, sharded=sharded)
    xs, evs = [], []
    led = {leg: [] for leg in comm.CommLedger.LEGS}
    t = 0
    while t < t1:
        steps = min(chunk, t1 - t)
        carry, ys = rounds.run_chunk(spec, batch, basisb, x0, carry, t,
                                     steps, root, sharded=sharded)
        xs.append(np.asarray(ys[0]))
        evs.append(np.asarray(ys[2]))
        for leg in comm.CommLedger.LEGS:
            led[leg].append(np.asarray(getattr(ys[1], leg)))
        t += steps
    return (np.concatenate(xs),
            {k: np.concatenate(v) for k, v in led.items()},
            np.concatenate(evs))


def _assert_streams_equal(a, b):
    np.testing.assert_array_equal(a[0], b[0])
    for leg in comm.CommLedger.LEGS:
        np.testing.assert_array_equal(a[1][leg], b[1][leg])
    np.testing.assert_array_equal(a[2], b[2])


def _uncached_reference(problem, sharded):
    progcache.deactivate()
    rounds.clear_aot_memo()
    return _serve_rounds(problem, sharded=sharded)


def _entry_files(cache_dir, kind, ext):
    return sorted(f for f in os.listdir(cache_dir)
                  if f.startswith(kind + "-") and f.endswith(ext))


# ==========================================================================
# Hit == miss == uncached, both reducers
# ==========================================================================
@pytest.mark.parametrize("sharded", [False, True],
                         ids=["vmap", "shard_map"])
def test_miss_then_hit_bitwise_equal_uncached(problem, tmp_path, sharded):
    ref = _uncached_reference(problem, sharded)

    root = str(tmp_path / "pc")
    cache = progcache.activate(root)
    try:
        rounds.clear_aot_memo()
        missed = _serve_rounds(problem, sharded=sharded)
        assert cache.stats["miss"] > 0 and cache.stats["hit"] == 0
        assert _entry_files(root, "serve_chunk", ".bin"), \
            "miss did not persist the chunk executable"

        # drop the in-process memo: the next dispatch must come back
        # through the on-disk cache as a deserialize hit
        rounds.clear_aot_memo()
        hit = _serve_rounds(problem, sharded=sharded)
        assert cache.stats["hit"] > 0
        assert cache.stats["miss"] == cache.stats["absent"]  # no new class

        _assert_streams_equal(missed, ref)
        _assert_streams_equal(hit, ref)
    finally:
        progcache.deactivate()
        rounds.clear_aot_memo()


# ==========================================================================
# Every miss class falls back to a live compile with identical bits
# ==========================================================================
def _populated(problem, cache_dir):
    out = _serve_rounds(problem)
    rounds.clear_aot_memo()
    return out


def test_corrupt_payload_falls_back_bitwise(problem, cache_dir):
    ref = _populated(problem, cache_dir)
    for f in _entry_files(cache_dir, "serve_chunk", ".bin"):
        path = os.path.join(cache_dir, f)
        blob = bytearray(open(path, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        open(path, "wb").write(bytes(blob))

    again = _serve_rounds(problem)
    assert progcache.active().stats["corrupt"] > 0
    _assert_streams_equal(again, ref)


def test_torn_manifest_falls_back_bitwise(problem, cache_dir):
    ref = _populated(problem, cache_dir)
    for f in _entry_files(cache_dir, "serve_chunk", ".json"):
        path = os.path.join(cache_dir, f)
        raw = open(path, "rb").read()
        open(path, "wb").write(raw[: len(raw) // 2])   # torn mid-write

    again = _serve_rounds(problem)
    assert progcache.active().stats["corrupt"] > 0
    _assert_streams_equal(again, ref)


def test_version_skew_falls_back_bitwise(problem, cache_dir):
    ref = _populated(problem, cache_dir)
    for f in _entry_files(cache_dir, "serve_chunk", ".json"):
        path = os.path.join(cache_dir, f)
        manifest = json.load(open(path))
        manifest["env"]["jax"] = "0.0.0-somebody-upgraded"
        json.dump(manifest, open(path, "w"))

    again = _serve_rounds(problem)
    assert progcache.active().stats["skew"] > 0
    _assert_streams_equal(again, ref)


def test_schema_version_bump_falls_back(problem, cache_dir):
    ref = _populated(problem, cache_dir)
    for f in _entry_files(cache_dir, "serve_chunk", ".json"):
        path = os.path.join(cache_dir, f)
        manifest = json.load(open(path))
        manifest["schema"] = "repro.progcache/entry@0"
        json.dump(manifest, open(path, "w"))

    again = _serve_rounds(problem)
    assert progcache.active().stats["skew"] > 0
    _assert_streams_equal(again, ref)


# ==========================================================================
# Cache keys
# ==========================================================================
def test_pallas_flag_keys_distinct_entries(monkeypatch):
    monkeypatch.setenv("REPRO_BL_PALLAS", "0")
    k0 = progcache.entry_key(("serve_chunk", "specfp"))
    monkeypatch.setenv("REPRO_BL_PALLAS", "1")
    k1 = progcache.entry_key(("serve_chunk", "specfp"))
    assert k0 != k1, ("REPRO_BL_PALLAS reroutes top-k selection, so the "
                      "two program families must land under distinct keys")


def test_fingerprint_deterministic_and_discriminating(problem):
    spec = problem[0]
    a, b = progcache.fingerprint(spec), progcache.fingerprint(spec)
    assert a == b
    # rebuild an equivalent spec from scratch: same fingerprint even
    # though every closure/callable inside it is a fresh object
    from repro.core import glm
    from repro.core.basis import orth_basis_from_data

    clients = glm.make_synthetic(seed=0, n_clients=6, m=24, d=18, r=6,
                                 lam=1e-3)
    bases = [orth_basis_from_data(c.A) for c in clients]
    spec2, _, _ = batched.bl2_setup(
        clients, bases, [TopK(k=6) for _ in clients],
        [Identity() for _ in clients], tau=3)
    assert progcache.fingerprint(spec2) == a
    spec3, _, _ = batched.bl2_setup(
        clients, bases, [TopK(k=6) for _ in clients],
        [Identity() for _ in clients], tau=2)
    assert progcache.fingerprint(spec3) != a


def test_env_fingerprint_is_hostname_free():
    import platform
    import socket

    fp = progcache.env_fingerprint()
    blob = json.dumps(fp)
    for ident in (socket.gethostname(), platform.node()):
        if ident:
            assert ident not in blob
    assert {"jax", "jaxlib", "backend", "device_count",
            "pallas"} <= set(fp)


# ==========================================================================
# Entry validation (tools/schema_diff.py --progcache rides on this)
# ==========================================================================
def test_validate_entry_accepts_real_and_rejects_corrupt(problem, cache_dir):
    _populated(problem, cache_dir)
    manifests = (_entry_files(cache_dir, "serve_init", ".json")
                 + _entry_files(cache_dir, "serve_chunk", ".json"))
    assert manifests
    for f in manifests:
        assert progcache.validate_entry(os.path.join(cache_dir, f)) == []

    target = os.path.join(cache_dir, manifests[0])
    bpath = target[: -len(".json")] + ".bin"
    open(bpath, "ab").write(b"junk")
    problems = progcache.validate_entry(target)
    assert problems and "sha256 mismatch" in problems[0]


def test_from_env_respects_disable(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_PROGCACHE_DIR", str(tmp_path / "envpc"))
    monkeypatch.setenv("REPRO_PROGCACHE", "0")
    assert progcache.from_env() is None
    monkeypatch.setenv("REPRO_PROGCACHE", "1")
    xla_dir = jax.config.jax_compilation_cache_dir
    cache = progcache.from_env()
    try:
        assert cache is not None
        assert cache.root == str(tmp_path / "envpc")
        # activating tier 1 leaves jax's compile cache (tier 2) where it was
        assert jax.config.jax_compilation_cache_dir == xla_dir
    finally:
        progcache.deactivate()


# ==========================================================================
# The package's source keys every entry
# ==========================================================================
def test_changed_source_digest_misses_unchanged_hits(problem, cache_dir, monkeypatch):
    """An executable compiled from other code is never loaded: entries are
    keyed on `progcache.source_digest`, so the same spec and shapes under
    another digest miss, and under the same digest hit."""
    cache = progcache.active()
    _serve_rounds(problem)                      # populate
    rounds.clear_aot_memo()
    hits, misses = cache.stats["hit"], cache.stats["miss"]
    _serve_rounds(problem)
    assert cache.stats["hit"] > hits and cache.stats["miss"] == misses

    monkeypatch.setattr(progcache, "source_digest", lambda: "0" * 32)
    rounds.clear_aot_memo()
    hits, misses = cache.stats["hit"], cache.stats["miss"]
    other = _serve_rounds(problem)
    assert cache.stats["hit"] == hits and cache.stats["miss"] > misses
    _assert_streams_equal(other, _uncached_reference(problem, False))


def test_source_digest_follows_the_python_source(tmp_path):
    pkg = tmp_path / "pkg"
    (pkg / "sub").mkdir(parents=True)
    (pkg / "a.py").write_text("x = 1\n")
    (pkg / "sub" / "b.py").write_text("y = 2\n")
    (pkg / "notes.txt").write_text("not source\n")
    digest = progcache.source_digest.__wrapped__     # uncached
    d0 = digest(str(pkg))
    assert digest(str(pkg)) == d0
    (pkg / "notes.txt").write_text("edited\n")
    assert digest(str(pkg)) == d0
    (pkg / "sub" / "b.py").write_text("y = 3\n")
    assert digest(str(pkg)) != d0
    assert progcache.source_digest() == digest(progcache.PACKAGE_DIR)
    assert os.path.basename(progcache.PACKAGE_DIR) == "repro"
