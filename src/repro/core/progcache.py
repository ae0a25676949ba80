"""Two-tier program cache: compile-free serve-loop start-up.

Every process start — cold launch or post-crash restart — used to pay a
full retrace + XLA compile of the chunked scan program before round 1
could run, which for the 8-device shard_map and cohort backends dwarfs the
~4 ms compiled per-round cost (seconds of compile vs milliseconds of
round).  This module makes start-up a *load*:

  * **Tier 1 — AOT executable cache.**  The serve programs
    (`rounds.init_serve_carry` / `rounds.run_chunk` / the cohort chunk
    program) are lowered and compiled ahead of time
    (``jitted.lower(*args).compile()``), serialized with
    `jax.experimental.serialize_executable`, and persisted as

        <cache_dir>/<name>-<key>.bin     pickled (payload, in_tree, out_tree)
        <cache_dir>/<name>-<key>.json    manifest (schema, sha256, env, aux)

    ``<key>`` is a sha256 digest of the program identity: the caller's key
    parts (the package's :func:`source_digest`, method-spec fingerprint,
    backend scope, abstract arg shapes/dtypes) plus the full
    :func:`env_fingerprint` — jax/jaxlib/XLA
    versions, backend, device count, and the ``REPRO_BL_PALLAS`` kernel
    flag.  A warm restart deserializes the executable in tens of
    milliseconds instead of recompiling in seconds.

  * **Tier 2 — JAX persistent compilation cache.**  Everything the AOT
    layer doesn't own (gap-stream evaluations, one-off partial-chunk
    lengths, problem construction, dry-run compiles) still goes through
    ``jax.jit``; :func:`enable_compile_cache` persists those compiles in
    the directory ``JAX_COMPILATION_CACHE_DIR`` names, or else in one
    fixed directory of the checkout (:data:`COMPILE_CACHE_DIR`).  It is
    independent of where tier 1 lives.

Fallback contract: *any* anomaly — missing entry, torn payload, sha256
mismatch, schema or environment skew, a deserialization error — is a MISS,
never an error: the program live-compiles from the identical lowering and
the freshly stored entry replaces the bad one.  Because the cache stores
the executable itself (not a re-derivation recipe), a cache hit runs the
byte-identical program a miss would have compiled — trajectories are
bitwise-equal either way (measured, not assumed: tests/test_progcache.py
and the ``cold_start`` bench record).

Writes follow the `repro.exp.artifacts` checkpoint idiom: tmp file +
``os.replace`` + directory fsync, payload before manifest, so a crash
mid-write leaves at worst an orphaned ``.bin`` that no manifest points at.

Activation: nothing happens unless a cache is active.  `repro.launch.
fed_serve` activates one per serve (``--progcache-dir``, default
``<ckpt_dir>/progcache``); any process can opt in via the
``REPRO_PROGCACHE_DIR`` environment variable (``REPRO_PROGCACHE=0``
force-disables).  With no active cache the round engine's dispatch path is
byte-for-byte the plain jitted fast path — zero added work.  The entry
points (`repro.launch.fed_serve`, ``python -m repro.exp``,
``chip_smoke.py``) turn on tier 2 with :func:`enable_compile_cache`.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import hashlib
import json
import os
import pickle
import sys
import time
from typing import Any, Callable, Optional, Tuple

import numpy as np

# v2: entries must come from donation-free lowerings (`rounds._chunk_jit_aot`
# and twins) — v1 entries serialized donating programs, which corrupt chained
# carry calls after deserialization, so they are invalidated wholesale
SCHEMA_VERSION = 2
#: manifest schema tag of one AOT cache entry (re-exported by
#: `repro.exp.artifacts` next to the checkpoint schemas; validated by
#: ``tools/schema_diff.py --progcache``)
PROGCACHE_SCHEMA = f"repro.progcache/entry@{SCHEMA_VERSION}"

#: kernel-routing flag that changes traced programs (Pallas top-k selection)
_PALLAS_FLAG = "REPRO_BL_PALLAS"


# ==========================================================================
# Environment fingerprint (cache-key tier + BENCH_*.json metadata)
# ==========================================================================
def env_fingerprint() -> dict:
    """The compilation environment as plain JSON data — everything that can
    change what an identical lowering compiles to (jax/jaxlib/XLA versions,
    backend, device population) plus the repo's own program-shaping flag
    (``REPRO_BL_PALLAS``).  Deliberately hostname-free: the same wheel on a
    different machine of the same shape shares cache entries, and
    ``BENCH_*.json`` records (which embed this dict) stay comparable
    across machines without leaking identity."""
    import platform

    import jax
    import jaxlib

    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "device_kind": jax.devices()[0].device_kind,
        "python": "%d.%d.%d" % sys.version_info[:3],
        "machine": platform.machine(),
        "pallas": os.environ.get(_PALLAS_FLAG, "0"),
    }


# ==========================================================================
# Deterministic object fingerprints (the cache-key spec tier)
# ==========================================================================
def fingerprint(obj: Any) -> str:
    """Process-stable canonical string for a cache-key object.

    Method specs are frozen dataclasses, but several hold *callables*
    (compressors close over budgets, the BL-DNN spec closes over loss/eval
    functions), whose ``repr`` embeds process-local addresses.  This walks
    the object structurally instead: dataclasses by qualified class name +
    field fingerprints, functions by ``module.qualname`` + defaults +
    closure-cell contents (addresses excluded), arrays by shape/dtype +
    content sha256, containers recursively.  Two processes building the
    same spec the same way produce the same string; anything unrecognized
    degrades to a type marker (worst case: a spurious cache miss, which
    just live-compiles)."""
    return _fp(obj, seen=set(), depth=0)


def _fp(o: Any, *, seen: set, depth: int) -> str:
    if depth > 10:
        return "<depth>"
    if o is None or isinstance(o, (bool, int, str)):
        return repr(o)
    if isinstance(o, float):
        return float.hex(o)
    if isinstance(o, bytes):
        return f"bytes:{hashlib.sha256(o).hexdigest()[:16]}"
    oid = id(o)
    if oid in seen:
        return "<cycle>"
    seen = seen | {oid}
    rec = functools.partial(_fp, seen=seen, depth=depth + 1)
    if isinstance(o, (tuple, list)):
        return "[" + ",".join(rec(v) for v in o) + "]"
    if isinstance(o, dict):
        items = sorted(o.items(), key=lambda kv: repr(kv[0]))
        return "{" + ",".join(f"{rec(k)}:{rec(v)}" for k, v in items) + "}"
    if isinstance(o, functools.partial):
        return (f"partial({rec(o.func)},{rec(tuple(o.args))},"
                f"{rec(dict(o.keywords))})")
    if dataclasses.is_dataclass(o) and not isinstance(o, type):
        fields = ",".join(
            f"{f.name}={rec(getattr(o, f.name))}"
            for f in dataclasses.fields(o))
        return f"{type(o).__module__}.{type(o).__qualname__}({fields})"
    if hasattr(o, "shape") and hasattr(o, "dtype"):
        try:
            arr = np.asarray(o)
            digest = hashlib.sha256(np.ascontiguousarray(arr)).hexdigest()[:16]
            return f"array({arr.shape},{arr.dtype},{digest})"
        except Exception:
            return (f"abstract({tuple(o.shape)},"
                    f"{np.dtype(o.dtype).name})")
    if callable(o):
        qual = (f"{getattr(o, '__module__', '?')}."
                f"{getattr(o, '__qualname__', type(o).__qualname__)}")
        cells = getattr(o, "__closure__", None) or ()
        closure = ",".join(rec(_cell_contents(c)) for c in cells)
        defaults = rec(getattr(o, "__defaults__", None))
        return f"fn({qual},defaults={defaults},closure=[{closure}])"
    return f"<{type(o).__module__}.{type(o).__qualname__}>"


def _cell_contents(cell):
    try:
        return cell.cell_contents
    except ValueError:          # empty cell
        return "<empty-cell>"


#: the `repro` package directory, whose source `source_digest` hashes
PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def source_digest(root: str = PACKAGE_DIR) -> str:
    """sha256 over the Python source under ``root`` (relative paths and
    bytes, in sorted order) — the code-version tier of a program key.  The
    structural `fingerprint` names functions by ``module.qualname``, not by
    their source, so without this an executable compiled from other code
    of the same names would load as a hit."""
    h = hashlib.sha256()
    for dirpath, dirnames, files in os.walk(root):
        dirnames.sort()
        for f in sorted(files):
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            h.update(os.path.relpath(path, root).encode() + b"\0")
            with open(path, "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()[:32]


def entry_key(key_parts: Tuple) -> str:
    """sha256 digest over (caller key parts, environment fingerprint) —
    the on-disk entry name.  Any environment change (jax upgrade, device
    population, ``REPRO_BL_PALLAS``) lands entries under new keys; the
    manifest's stored env is additionally equality-checked on load, so a
    digest can never resurrect a stale-environment executable."""
    blob = json.dumps([[str(p) for p in key_parts], env_fingerprint()],
                      sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:32]


# ==========================================================================
# Atomic file plumbing (the artifacts.py checkpoint idiom)
# ==========================================================================
def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def _atomic_write(path: str, data: bytes) -> None:
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    try:
        dfd = os.open(os.path.dirname(path) or ".", os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:
        pass


def _ensure_runtime_kernels() -> None:
    """Register the CPU runtime's legacy custom-call targets before running
    a deserialized executable.  jaxlib registers them lazily inside its
    LOWERING helpers (`jaxlib/lapack.py` calls ``_lapack.initialize()``
    from ``trsm_hlo`` etc.), so a process that only ever deserializes —
    never lowers — would hand XLA a program whose ``blas_dtrsm`` /
    ``lapack_*`` symbols were never registered and segfault at dispatch."""
    try:
        from jaxlib.cpu import _lapack

        _lapack.initialize()
    except Exception:   # non-CPU-only jaxlib layouts; GPU registers eagerly
        pass


@contextlib.contextmanager
def _compile_cache_off():
    """Compile with jax's persistent compilation cache (tier 2) off.  An
    executable that jax loaded from its own cache serializes into a
    payload that fails at dispatch once loaded back (XLA:CPU, jax 0.9:
    "Function ... not found"), so a tier-1 entry is always compiled
    afresh.  jax latches whether its cache is in use at the first compile,
    hence the resets on the way in and out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


# ==========================================================================
# The cache
# ==========================================================================
class ProgramCache:
    """One AOT executable cache directory (tier 1).

    ``stats`` counts dispatch outcomes (``hit`` / ``miss`` and the miss
    reasons ``absent`` / ``corrupt`` / ``skew`` / ``load_error``, plus
    ``store_error`` for failed writes); ``events`` keeps the per-program
    outcome log the serve loop reports in its record meta."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self.stats: collections.Counter = collections.Counter()
        self.events: list = []

    # ------------------------------------------------------------------
    def _paths(self, name: str, key: str) -> Tuple[str, str]:
        base = os.path.join(self.root, f"{name}-{key}")
        return base + ".bin", base + ".json"

    def load_manifest(self, name: str, key: str) -> Optional[dict]:
        _, mpath = self._paths(name, key)
        try:
            with open(mpath) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            return None

    def _load(self, name: str, key: str):
        """(compiled, why) — compiled is None on any miss; ``why`` names
        the miss class for stats."""
        bpath, mpath = self._paths(name, key)
        manifest = self.load_manifest(name, key)
        if manifest is None:
            return None, ("absent" if not os.path.exists(mpath)
                          else "corrupt")
        if manifest.get("schema") != PROGCACHE_SCHEMA:
            return None, "skew"
        if manifest.get("env") != env_fingerprint():
            return None, "skew"
        if not os.path.exists(bpath):
            return None, "corrupt"
        if _sha256_file(bpath) != manifest.get("payload_sha256"):
            return None, "corrupt"
        try:
            from jax.experimental import serialize_executable as se

            _ensure_runtime_kernels()
            with open(bpath, "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
            return se.deserialize_and_load(payload, in_tree, out_tree), "hit"
        except Exception:
            return None, "load_error"

    def _store(self, name: str, key: str, compiled, aux: Optional[dict]):
        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
            bpath, mpath = self._paths(name, key)
            _atomic_write(bpath, pickle.dumps((payload, in_tree, out_tree)))
            manifest = {
                "schema": PROGCACHE_SCHEMA,
                "name": name,
                "key": key,
                "payload_sha256": _sha256_file(bpath),
                "payload_bytes": os.path.getsize(bpath),
                "env": env_fingerprint(),
                "created_unix": time.time(),
                "aux": aux or {},
            }
            _atomic_write(
                mpath, (json.dumps(manifest, indent=1) + "\n").encode())
            return True
        except Exception:
            # unserializable program (exotic backend/custom call) — the
            # live-compiled executable still runs; only persistence is lost
            self.stats["store_error"] += 1
            return False

    # ------------------------------------------------------------------
    def load_or_compile(self, *, name: str, key_parts: Tuple,
                        lower: Callable[[], Any],
                        aux: Optional[dict] = None):
        """The dispatch primitive: return ``(compiled, status)`` where
        ``status`` is ``"hit"`` or the miss class that forced the live
        compile.  ``lower`` is called only on a miss and must return a
        ``jax.stages.Lowered``; the freshly compiled executable is stored
        back (best-effort) so the next process hits."""
        key = entry_key(key_parts)
        compiled, why = self._load(name, key)
        if compiled is not None:
            self.stats["hit"] += 1
            self.events.append({"name": name, "key": key, "status": "hit"})
            return compiled, "hit"
        self.stats["miss"] += 1
        self.stats[why] += 1
        with _compile_cache_off():
            compiled = lower().compile()
        self._store(name, key, compiled, aux)
        self.events.append({"name": name, "key": key, "status": why})
        return compiled, why

    def summary(self) -> dict:
        """Operational stats for record metadata (serve ``meta``)."""
        return {"dir": self.root, "stats": dict(self.stats),
                "programs": list(self.events)}


# ==========================================================================
# Active-cache plumbing + tier 2
# ==========================================================================
_ACTIVE: Optional[ProgramCache] = None


def active() -> Optional[ProgramCache]:
    """The process's active `ProgramCache`, or None (caching disabled)."""
    return _ACTIVE


def activate(root: str) -> ProgramCache:
    """Activate an AOT cache rooted at ``root`` (idempotent for the same
    directory).  Tier 2 is not touched: see `enable_compile_cache`."""
    global _ACTIVE
    if _ACTIVE is None or _ACTIVE.root != os.path.abspath(root):
        _ACTIVE = ProgramCache(root)
    return _ACTIVE


def deactivate() -> None:
    global _ACTIVE
    _ACTIVE = None


#: tier 2's directory when ``JAX_COMPILATION_CACHE_DIR`` is not set: one
#: fixed path inside the checkout (git-ignored).  The cache hits only
#: where the path stays the same, so it never names a pid, a time or a
#: temporary directory.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Tier 2: persist every jit compile this process does (below the AOT
    layer — problem construction, partial-chunk lengths, gap-stream
    evals) in jax's own on-disk compilation cache, and return its
    directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has read it at import
    and this sets no directory of its own.  Otherwise the cache goes to
    `COMPILE_CACHE_DIR`.  Thresholds are zeroed so CPU-fast programs
    cache too (jax's defaults skip sub-second compiles)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != COMPILE_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
        # jax initializes its cache AT MOST ONCE per process, latching
        # whatever `jax_compilation_cache_dir` held at the first compile;
        # entry points compile before they get here (problem and fleet
        # construction), so the latch holds `None` — reset it, or the
        # cache silently never engages
        cc.reset_cache()
    return COMPILE_CACHE_DIR


def from_env() -> Optional[ProgramCache]:
    """Honor ``REPRO_PROGCACHE_DIR`` (subprocess benches and tests opt in
    through the environment; ``REPRO_PROGCACHE=0`` force-disables)."""
    if os.environ.get("REPRO_PROGCACHE", "1") == "0":
        return None
    root = os.environ.get("REPRO_PROGCACHE_DIR")
    if not root:
        return _ACTIVE
    return activate(root)


def validate_entry(manifest_path: str) -> list:
    """Schema-validate one cache-entry manifest (``tools/schema_diff.py
    --progcache``); returns a list of problem strings (empty = valid)."""
    problems = []
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{manifest_path}: unreadable manifest ({e})"]
    if manifest.get("schema") != PROGCACHE_SCHEMA:
        problems.append(f"{manifest_path}: schema "
                        f"{manifest.get('schema')!r} != {PROGCACHE_SCHEMA!r}")
    for req in ("name", "key", "payload_sha256", "env"):
        if req not in manifest:
            problems.append(f"{manifest_path}: missing key {req!r}")
    bpath = manifest_path[:-len(".json")] + ".bin"
    if "payload_sha256" in manifest:
        if not os.path.exists(bpath):
            problems.append(f"{manifest_path}: payload {bpath} missing")
        elif _sha256_file(bpath) != manifest["payload_sha256"]:
            problems.append(f"{manifest_path}: payload sha256 mismatch")
    return problems


# a process that opts in via the environment gets its cache at import time,
# before any serve program dispatches
from_env()
