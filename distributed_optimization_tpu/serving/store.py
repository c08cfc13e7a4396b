"""Persistent on-disk executable store (ISSUE-15 tentpole; docs/SERVING.md).

The in-memory ``ExecutableCache`` dies with the process, so every daemon
restart re-pays the 4–6 s whole-run cold compile (docs/PERF.md §3) for
every structural class it serves — the single largest latency cliff left
in the serving plane. This module makes the compiled programs themselves
durable: each ``CacheEntry`` is serialized through jax's AOT executable
serialization (``jax.experimental.serialize_executable`` — the same
pickled-unloaded-executable machinery the persistent compilation cache
uses) into one file per cache key, and a restarted process deserializes
and *loads* the executable instead of recompiling. A store-warm request
reports ``compile_seconds == 0.0`` and produces bitwise the result the
original compile produced (tests/test_exec_store.py pins both).

Contract decisions, and why:

- **Keyed by the full cache key.** Files are named by the SHA-256 of the
  exact in-memory cache key tuple (``serving/cache.py`` key builders:
  structural hash + sequential full-config hash, dataset/mesh/schedule
  signatures, x64 + device identity). The store never invents its own
  weaker key — anything that would miss the RAM cache also misses the
  store, so the two tiers can never disagree about what "the same
  program" means. The key's repr is stored inside the artifact and
  re-checked on load (a digest collision or a repr-format drift reads as
  a miss, never as the wrong program).
- **Provenance-guarded loads.** An artifact records the producing
  environment — ``jax.__version__`` and device kind from
  ``telemetry.provenance()``, plus the x64 mode — and a mismatched
  artifact is *skipped with one warning*, not deserialized and crashed
  on: serialized XLA executables are not portable across jax versions or
  device kinds, and a redeploy that upgrades jax must degrade to a cold
  compile, not a corrupt-program crash.
  An artifact also records the ids of the devices its program runs over
  (a mesh of 4 workers takes 4 of 8 visible devices) and is loaded onto
  exactly those: left to jax, a load binds every visible device and the
  first call fails on the shard count. Where the process lacks one of
  them the artifact is skipped the same way.
- **Corruption degrades to a cold compile.** A truncated, unreadable or
  wrong-schema artifact logs a single warning per file and reads as a
  miss — mirroring the ISSUE-3 checkpoint-fallback contract
  (``RunCheckpointer.restore`` skipping partial chunks). The store never
  raises into the serving path.
- **Atomic writes.** Artifacts are written to a temp file and
  ``os.replace``d into place, so a crash mid-write leaves either the old
  artifact or none — a concurrently restarting worker can never observe
  a half-written program. Multiple worker processes share one store
  directory safely this way (last writer wins; they write identical
  payloads for identical keys).

``DOPT_EXEC_STORE=<dir>`` attaches a store to the process-wide default
cache (``serving/cache.py``) — the env var is how spawned serving workers
inherit the shared warm tier without any plumbing.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
import tempfile
import threading
import time
from typing import Any, Optional

from distributed_optimization_tpu.log import get_logger

_log = get_logger("serving.store")

STORE_SCHEMA_VERSION = 1
# One file per compiled program; the suffix marks the format so a store
# directory can be swept/inspected without parsing anything else in it.
ARTIFACT_SUFFIX = ".dopt-exec"

_ENV_VAR = "DOPT_EXEC_STORE"


def key_digest(key: tuple) -> str:
    """Stable on-disk name for a cache key: SHA-256 of its repr.

    The key tuples are built from primitives (strings, ints, floats,
    bools, None, nested tuples), whose reprs are deterministic across
    processes — the property the restart-warm gate rides on.
    """
    return hashlib.sha256(repr(key).encode()).hexdigest()


def store_provenance() -> dict:
    """The environment facts an artifact must match to be loadable:
    serialized XLA executables bind the producing jax version, the
    device kind they were compiled for, and the x64 mode (weak-typed
    scalar promotion changes programs)."""
    from distributed_optimization_tpu import telemetry

    prov = telemetry.provenance()
    x64 = None
    try:
        import jax

        x64 = bool(jax.config.jax_enable_x64)
    except Exception:
        pass
    return {
        "jax_version": prov.get("jax_version"),
        "device_kind": prov.get("device_kind"),
        "x64": x64,
    }


@dataclasses.dataclass
class StoreStats:
    """Lifetime counters (all plain ints/floats — JSON-safe)."""

    saves: int = 0
    save_errors: int = 0
    load_hits: int = 0
    load_misses: int = 0
    skipped_provenance: int = 0
    corrupt: int = 0
    load_seconds: float = 0.0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class PersistentExecutableStore:
    """Write-through/load-on-miss disk tier under an ``ExecutableCache``.

    Thread-safe; shared across worker processes via the filesystem (see
    the module docstring for the atomicity argument). All failure paths
    warn once per artifact and degrade to a miss.
    """

    def __init__(self, root) -> None:
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)
        self._lock = threading.Lock()
        self._stats = StoreStats()
        self._warned: set[str] = set()  # one warning per artifact file
        self._provenance: Optional[dict] = None  # resolved on first use
        # Corruption observers (ISSUE-16 fleet remediation): called as
        # ``fn(path, detail)`` when an artifact reads as corrupt. The
        # store itself only degrades to a miss; a listener may choose to
        # quarantine the file so the next load is a clean miss instead of
        # re-reading the same damage.
        self._corruption_listeners: list = []
        # Registry families (ISSUE-10 conventions): labeled result
        # counter so a dashboard separates warm loads from provenance
        # skips without scraping logs.
        from distributed_optimization_tpu.observability.metrics_registry import (  # noqa: E501
            metrics_registry,
        )

        reg = metrics_registry()
        self._m_loads = reg.counter(
            "dopt_exec_store_loads_total",
            "Persistent-store load attempts by result "
            "(hit/miss/provenance_mismatch/corrupt)",
        )
        self._m_saves = reg.counter(
            "dopt_exec_store_saves_total",
            "Executables persisted to the on-disk store (error=save "
            "failures, skipped without raising)",
        )

    # ------------------------------------------------------------ plumbing
    def _path(self, key: tuple) -> str:
        return os.path.join(self.root, key_digest(key) + ARTIFACT_SUFFIX)

    def _prov(self) -> dict:
        # Resolved lazily (jax import) and cached: every load/save checks
        # it, and it cannot change within a process.
        if self._provenance is None:
            self._provenance = store_provenance()
        return self._provenance

    def _warn_once(self, path: str, message: str) -> None:
        with self._lock:
            if path in self._warned:
                return
            self._warned.add(path)
        _log.warning("%s — falling back to a cold compile", message)

    def add_corruption_listener(self, fn) -> None:
        """Register ``fn(path, detail)`` to run when an artifact reads as
        corrupt (truncated pickle, schema/key mismatch, undeserializable
        payload). Listener failures are swallowed — remediation must
        never break the degrade-to-miss contract."""
        with self._lock:
            self._corruption_listeners.append(fn)

    def _notify_corrupt(self, path: str, detail: str) -> None:
        with self._lock:
            listeners = list(self._corruption_listeners)
        for fn in listeners:
            try:
                fn(path, detail)
            except Exception:
                pass

    # ------------------------------------------------------------- writing
    def save(self, key: tuple, entry) -> bool:
        """Persist one ``CacheEntry``; returns True on success.

        Serialization failures (exotic executables, full disk) warn once
        and return False — persistence is an optimization, never a
        reason to fail the request that just compiled successfully.
        """
        path = self._path(key)
        try:
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(
                entry.executable
            )
            blob = pickle.dumps({
                "schema": STORE_SCHEMA_VERSION,
                "provenance": self._prov(),
                "key_repr": repr(key),
                # The devices the program runs over (a worker mesh may
                # take fewer than the process sees): loading binds them.
                "device_ids": [
                    d.id for d in
                    entry.executable.runtime_executable().local_devices()
                ],
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
                "cost": entry.cost,
                "compile_seconds": float(entry.compile_seconds),
            }, protocol=pickle.HIGHEST_PROTOCOL)
            fd, tmp = tempfile.mkstemp(
                dir=self.root, suffix=ARTIFACT_SUFFIX + ".tmp"
            )
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)  # atomic: old artifact or new, never half
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except Exception as e:
            with self._lock:
                self._stats.save_errors += 1
            self._m_saves.inc(result="error")
            self._warn_once(
                path,
                f"could not persist executable to {path} "
                f"({type(e).__name__}: {e})",
            )
            return False
        with self._lock:
            self._stats.saves += 1
        self._m_saves.inc(result="ok")
        return True

    # ------------------------------------------------------------- loading
    def load(self, key: tuple):
        """Deserialize + load the artifact for ``key``, or None.

        Returns a ``serving.cache.CacheEntry`` ready to execute. Every
        failure mode — missing file, truncated/unreadable pickle, schema
        or key mismatch, provenance mismatch — returns None (a miss) and
        the non-missing ones warn once per file.
        """
        from distributed_optimization_tpu.serving.cache import (
            CacheEntry,
            estimate_executable_bytes,
        )

        path = self._path(key)
        if not os.path.exists(path):
            with self._lock:
                self._stats.load_misses += 1
            self._m_loads.inc(result="miss")
            return None
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as f:
                record = pickle.load(f)
            if not isinstance(record, dict) or record.get("schema") != (
                STORE_SCHEMA_VERSION
            ):
                raise ValueError(
                    f"unsupported store schema "
                    f"{record.get('schema') if isinstance(record, dict) else type(record).__name__!r}"  # noqa: E501
                )
            if record.get("key_repr") != repr(key):
                raise ValueError("stored key does not match (digest collision"
                                 " or key-format drift)")
        except Exception as e:
            with self._lock:
                self._stats.corrupt += 1
                self._stats.load_misses += 1
            self._m_loads.inc(result="corrupt")
            self._warn_once(
                path,
                f"corrupt/unreadable store artifact {path} "
                f"({type(e).__name__}: {e})",
            )
            self._notify_corrupt(path, f"{type(e).__name__}: {e}")
            return None
        stored_prov = record.get("provenance") or {}
        here = self._prov()
        mismatched = {
            k: (stored_prov.get(k), here.get(k))
            for k in ("jax_version", "device_kind", "x64")
            if stored_prov.get(k) != here.get(k)
        }
        import jax

        devices = {d.id: d for d in jax.devices()}
        device_ids = record.get("device_ids")
        if device_ids is None or not set(device_ids) <= set(devices):
            mismatched["device_ids"] = (device_ids, sorted(devices))
        if mismatched:
            with self._lock:
                self._stats.skipped_provenance += 1
                self._stats.load_misses += 1
            self._m_loads.inc(result="provenance_mismatch")
            self._warn_once(
                path,
                f"skipping store artifact {path}: provenance mismatch "
                + ", ".join(
                    f"{k} {a!r} (stored) != {b!r} (here)"
                    for k, (a, b) in sorted(mismatched.items())
                ),
            )
            return None
        try:
            from jax.experimental import serialize_executable

            executable = serialize_executable.deserialize_and_load(
                record["payload"], record["in_tree"], record["out_tree"],
                execution_devices=[devices[i] for i in device_ids],
            )
        except Exception as e:
            with self._lock:
                self._stats.corrupt += 1
                self._stats.load_misses += 1
            self._m_loads.inc(result="corrupt")
            self._warn_once(
                path,
                f"could not deserialize store artifact {path} "
                f"({type(e).__name__}: {e})",
            )
            self._notify_corrupt(path, f"{type(e).__name__}: {e}")
            return None
        load_s = time.perf_counter() - t0
        with self._lock:
            self._stats.load_hits += 1
            self._stats.load_seconds += load_s
        self._m_loads.inc(result="hit")
        return CacheEntry(
            executable=executable,
            cost=record.get("cost"),
            compile_seconds=float(record.get("compile_seconds", 0.0)),
            est_bytes=estimate_executable_bytes(executable),
        )

    # ----------------------------------------------------------- inventory
    def __len__(self) -> int:
        try:
            return sum(
                1 for n in os.listdir(self.root)
                if n.endswith(ARTIFACT_SUFFIX)
            )
        except OSError:
            return 0

    def disk_bytes(self) -> int:
        total = 0
        try:
            for n in os.listdir(self.root):
                if n.endswith(ARTIFACT_SUFFIX):
                    try:
                        total += os.path.getsize(os.path.join(self.root, n))
                    except OSError:
                        pass
        except OSError:
            pass
        return total

    def stats(self) -> dict:
        with self._lock:
            out: dict[str, Any] = self._stats.as_dict()
        out["root"] = self.root
        out["artifacts"] = len(self)
        out["disk_bytes"] = self.disk_bytes()
        return out


# ----------------------------------------------------- process-wide default

_process_store: Optional[PersistentExecutableStore] = None
_process_store_root: Optional[str] = None
_store_lock = threading.Lock()


def process_store_root() -> Optional[str]:
    """The env-configured store directory (``DOPT_EXEC_STORE``), or None."""
    root = os.environ.get(_ENV_VAR, "").strip()
    return root or None


def process_executable_store() -> Optional[PersistentExecutableStore]:
    """The process-wide store named by ``DOPT_EXEC_STORE`` (None when the
    env var is unset). One instance per configured root — re-pointing the
    env var mid-process builds a fresh instance, which only tests do."""
    root = process_store_root()
    if root is None:
        return None
    global _process_store, _process_store_root
    with _store_lock:
        if _process_store is None or _process_store_root != root:
            _process_store = PersistentExecutableStore(root)
            _process_store_root = root
        return _process_store
