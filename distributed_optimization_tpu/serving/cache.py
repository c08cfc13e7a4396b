"""AOT executable cache (ISSUE-7 tentpole; docs/SERVING.md).

Every jax execution path compiles the ENTIRE run into one XLA program and,
until this layer existed, re-traced and re-compiled it on every call — the
4–6 s line item docs/PERF.md §3 measures, paid per CLI invocation, per
``Simulator.run_one``, per bench variant. The compiled executable itself is
reusable: data shards, PRNG keys, fault timelines, Byzantine masks, swept
scalars and (on the batched path) f* are all traced INPUTS, so any request
whose config compiles to the same program can re-execute a cached
executable with its own inputs and get bit-for-bit the result a fresh
compile would have produced (tests/test_serving.py pins it).

What IS baked into a program — and therefore what a cache key must carry —
differs per path, so the key builders live here next to the cache:

- both paths bake the topology's realized constants (mixing weights,
  degrees, neighbor tables) and everything ``ExperimentConfig
  .structural_dict`` covers;
- the SEQUENTIAL program additionally bakes the run seed (its PRNG key is
  a closure constant), the unswept hyperparameter scalars, and f*, so its
  key is the full config hash — reuse means "the identical experiment
  again" (exactly the ``make smoke`` / repeated-CLI-invocation waste);
- the BATCHED program takes seeds/sweeps/f* as data, so its key is the
  STRUCTURAL hash plus call-level facts (cohort size R, t0, which rp
  inputs exist, data shapes) — reuse spans seed and sweep variants, which
  is what the serving coalescer trades on.

Entries are LRU-evicted by count AND estimated bytes; hits, misses,
evictions and compile-seconds-saved are counted for the serving telemetry
(``telemetry.health_summary(serving=...)``, ``format_report``).

A process-wide default instance is consulted by ``jax_backend.run`` /
``run_batch`` when the caller passes ``executable_cache=None`` (pass
``False`` to force a cold compile; set ``DOPT_EXEC_CACHE=0`` to disable
the default for a whole process).
"""

from __future__ import annotations

import dataclasses
import os
import threading
from collections import OrderedDict
from typing import Any, Optional

from distributed_optimization_tpu.config import SWEEPABLE_FIELDS
from distributed_optimization_tpu.observability import device_scopes

# Default LRU bounds: enough distinct programs for a bench/smoke session
# without letting a long-lived daemon accumulate unbounded compiled code.
DEFAULT_MAX_ENTRIES = 64
DEFAULT_MAX_BYTES = 2_000_000_000
# Conservative per-entry estimate when XLA's memory analysis is unavailable
# (CPU builds often report nothing): small-config CPU executables measure
# well under this, so the bytes bound stays a bound, not a fiction.
FALLBACK_ENTRY_BYTES = 8_000_000

_DISABLE_ENV = "DOPT_EXEC_CACHE"


def estimate_executable_bytes(executable) -> int:
    """Estimated resident size of a compiled executable.

    Prefers XLA's own ``memory_analysis`` (generated code + temp
    allocations); falls back to a fixed conservative estimate — eviction
    accounting is telemetry-adjacent, never control flow worth raising for.
    """
    try:
        # The one memory_analysis() call an executable gets: the run
        # builder's ``temp_bytes`` reads the same answer.
        size = sum(device_scopes.memory(executable).values())
        if size > 0:
            return size
    except Exception:
        pass
    return FALLBACK_ENTRY_BYTES


@dataclasses.dataclass
class CacheEntry:
    """One cached compiled program + the provenance its reuse reports."""

    executable: Any
    cost: Optional[dict]  # telemetry.cost_from_lowered of the cold lowering
    compile_seconds: float  # what the cold compile cost (== what a hit saves)
    est_bytes: int
    hits: int = 0


class ExecutableCache:
    """LRU cache of compiled XLA executables, keyed by opaque tuples.

    Thread-safe (the serving daemon submits from HTTP handler threads).
    Keys are built by the ``sequential_cache_key``/``batch_cache_key``
    helpers below — the cache itself never inspects configs.
    """

    def __init__(
        self,
        max_entries: int = DEFAULT_MAX_ENTRIES,
        max_bytes: int = DEFAULT_MAX_BYTES,
        store=None,
    ):
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        if max_bytes < 1:
            raise ValueError(f"max_bytes must be >= 1, got {max_bytes}")
        self.max_entries = max_entries
        self.max_bytes = max_bytes
        # Optional persistent disk tier (serving/store.py, ISSUE-15):
        # get() falls through to it on a memory miss, put() writes
        # through to it — both outside the cache lock (disk I/O and
        # executable deserialization must not serialize lookups).
        self.store = store
        self._entries: "OrderedDict[tuple, CacheEntry]" = OrderedDict()
        self._lock = threading.RLock()
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.store_hits = 0
        self.evictions = 0
        self.compile_seconds_saved = 0.0
        # Registry instrumentation (ISSUE-10): every cache instance feeds
        # the process-wide counters — a scrape sees the whole process's
        # compile amortization, whichever cache instances produced it.
        from distributed_optimization_tpu.observability.metrics_registry import (
            metrics_registry,
        )

        reg = metrics_registry()
        self._m_hits = reg.counter(
            "dopt_exec_cache_hits_total", "Executable-cache hits")
        self._m_misses = reg.counter(
            "dopt_exec_cache_misses_total", "Executable-cache misses")
        self._m_evictions = reg.counter(
            "dopt_exec_cache_evictions_total", "Executable-cache evictions")
        self._m_saved = reg.counter(
            "dopt_exec_cache_compile_seconds_saved_total",
            "Compile seconds avoided by executable-cache hits")

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def get(self, key: tuple) -> Optional[CacheEntry]:
        """Look up a compiled program; counts a hit or a miss either way.

        On a memory miss, falls through to the persistent store tier
        (when one is attached): a store hit deserializes the executable,
        promotes it into memory, and counts as a hit AND a ``store_hit``
        — callers see exactly the contract a memory hit gives them
        (``compile_seconds == 0.0`` on the reuse path), which is what the
        restart-warm gate measures.

        Registry counters are bumped AFTER the cache lock is released:
        the registry's render/snapshot path calls back into the cache
        (the entries/bytes gauges) while holding the registry lock, so
        touching the registry while holding the cache lock would be the
        classic ABBA deadlock against a concurrent ``/metrics`` scrape.
        Store I/O (disk read + executable load) happens outside the lock
        too — a multi-ms deserialize must not serialize other lookups.
        """
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                entry.hits += 1
                self.compile_seconds_saved += entry.compile_seconds
        if entry is not None:
            self._m_hits.inc()
            self._m_saved.inc(entry.compile_seconds)
            return entry
        loaded = self.store.load(key) if self.store is not None else None
        if loaded is None:
            with self._lock:
                self.misses += 1
            self._m_misses.inc()
            return None
        # Store hit: promote into memory (no write-back — it came from
        # disk) and account it as a hit the moment it is served.
        n_evicted = self._insert(key, loaded)
        with self._lock:
            self.hits += 1
            self.store_hits += 1
            loaded.hits += 1
            self.compile_seconds_saved += loaded.compile_seconds
        if n_evicted:
            self._m_evictions.inc(n_evicted)
        self._m_hits.inc()
        self._m_saved.inc(loaded.compile_seconds)
        return loaded

    def _insert(self, key: tuple, entry: CacheEntry) -> int:
        """Insert under the lock with LRU eviction; returns the eviction
        count for the caller to report outside the lock (see get())."""
        n_evicted = 0
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.est_bytes
            self._entries[key] = entry
            self._bytes += entry.est_bytes
            while len(self._entries) > self.max_entries or (
                self._bytes > self.max_bytes and len(self._entries) > 1
            ):
                _, evicted = self._entries.popitem(last=False)
                self._bytes -= evicted.est_bytes
                self.evictions += 1
                n_evicted += 1
        return n_evicted

    def put(
        self,
        key: tuple,
        executable,
        *,
        cost: Optional[dict] = None,
        compile_seconds: float = 0.0,
    ) -> CacheEntry:
        """Insert a freshly compiled program, evicting LRU entries past the
        count/bytes bounds (the newest entry itself is never evicted — an
        oversized program simply owns the cache until something replaces
        it). Write-through: when a persistent store is attached, the new
        program is serialized to disk so a future process starts warm."""
        entry = CacheEntry(
            executable=executable,
            cost=cost,
            compile_seconds=float(compile_seconds),
            est_bytes=estimate_executable_bytes(executable),
        )
        n_evicted = self._insert(key, entry)
        if n_evicted:  # outside the cache lock — see get()
            self._m_evictions.inc(n_evicted)
        if self.store is not None:
            # Outside the lock: serialization is slow and never
            # load-bearing (save() degrades to a warning on failure).
            self.store.save(key, entry)
        return entry

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0

    def attach_store(self, store) -> None:
        """Attach (or replace) the persistent disk tier after
        construction — how the daemon wires ``--store`` into the
        process-wide default cache."""
        self.store = store

    def stats(self) -> dict:
        """Counters for the serving telemetry block (all plain scalars
        except ``store``, which is the attached store's own stats dict or
        None — the key is ALWAYS present so the status shape does not
        depend on deployment)."""
        store = self.store
        store_stats = store.stats() if store is not None else None
        with self._lock:
            lookups = self.hits + self.misses
            return {
                "entries": len(self._entries),
                "est_bytes": int(self._bytes),
                "hits": int(self.hits),
                "misses": int(self.misses),
                "store_hits": int(self.store_hits),
                "evictions": int(self.evictions),
                "hit_rate": self.hits / lookups if lookups else None,
                "compile_seconds_saved": float(self.compile_seconds_saved),
                "store": store_stats,
            }

    @classmethod
    def empty_stats(cls) -> dict:
        """The zero-valued ``stats()`` shape, derived from a fresh
        instance so it CANNOT drift from the real one — the
        disabled-cache status block reuses it to keep the "counter keys
        always present" contract (docs/SERVING.md) as counters are
        added."""
        return cls().stats()


# ------------------------------------------------------- process-wide default

_process_cache: Optional[ExecutableCache] = None
_process_lock = threading.Lock()


def process_cache_enabled() -> bool:
    return os.environ.get(_DISABLE_ENV, "").lower() not in (
        "0", "off", "false", "no",
    )


def process_executable_cache() -> Optional[ExecutableCache]:
    """The process-wide default cache ``jax_backend`` consults when a caller
    passes ``executable_cache=None`` — what makes ``make smoke`` and
    repeated CLI invocations in one process compile each program once.
    ``DOPT_EXEC_CACHE=0`` disables it (returns None)."""
    if not process_cache_enabled():
        return None
    global _process_cache
    with _process_lock:
        if _process_cache is None:
            # ``DOPT_EXEC_STORE=<dir>`` attaches the persistent disk tier
            # (serving/store.py) to the process default — the env-var
            # form is what spawned serving workers inherit, so every
            # worker shares one warm store with zero plumbing.
            from distributed_optimization_tpu.serving.store import (
                process_executable_store,
            )

            _process_cache = ExecutableCache(
                store=process_executable_store()
            )
            # Scrape-time gauges for the process cache's current state
            # (entries/bytes are someone's source of truth, not events —
            # the registry polls them so they can never go stale).
            from distributed_optimization_tpu.observability.metrics_registry import (  # noqa: E501
                metrics_registry,
            )

            reg = metrics_registry()
            cache = _process_cache
            # The callbacks run under the REGISTRY lock (scrape time), so
            # they must not take the cache lock (ABBA vs get/put, which
            # bump registry counters) — plain attribute reads are atomic
            # enough for a gauge, and a one-entry-stale reading is fine.
            reg.gauge_fn(
                "dopt_exec_cache_entries",
                "Compiled programs resident in the process executable cache",
                lambda: len(cache._entries),
            )
            reg.gauge_fn(
                "dopt_exec_cache_bytes",
                "Estimated resident bytes of the process executable cache",
                lambda: cache._bytes,
            )
        return _process_cache


def resolve_cache(executable_cache) -> Optional[ExecutableCache]:
    """Resolve the backends' ``executable_cache`` argument: ``None`` → the
    process default, ``False`` → no caching (force a cold compile), an
    ``ExecutableCache`` → itself."""
    if executable_cache is None:
        return process_executable_cache()
    if executable_cache is False:
        return None
    return executable_cache


# ------------------------------------------------------------- key builders


def _full_config_hash(config) -> str:
    from distributed_optimization_tpu.telemetry import config_hash

    return config_hash(config.to_dict())


def _jax_env_signature() -> tuple:
    """The jax-global facts a trace bakes in beyond the config: the x64
    switch (weak-typed scalars promote under it) and the visible device
    set (platform, count, and identity — shardings bind to devices)."""
    import jax

    return (
        bool(jax.config.jax_enable_x64),
        tuple(str(d) for d in jax.devices()),
    )


def dataset_signature(device_data) -> tuple:
    """What a compiled program pins about its data INPUTS: shapes and
    dtypes — the values themselves are traced arguments — plus the
    per-worker valid counts, which feed host-side branch decisions
    (the full-batch fast path)."""
    return (
        tuple(device_data.X.shape),
        str(device_data.X.dtype),
        str(device_data.y.dtype),
        tuple(int(v) for v in device_data.n_valid),
    )


def sequential_cache_key(
    config,
    f_opt: float,
    device_data,
    *,
    schedule_signature=None,
    collect_metrics: bool = True,
    mesh_signature=None,
    segment=None,
) -> tuple:
    """Cache key for the sequential scan's program (``_run``). Everything
    per-run is baked there — the PRNG key, the hyperparameter scalars, f*
    — so the key is the FULL config hash plus the call-level knobs that
    alter the trace. ``segment`` is the size, in evals, of the segment the
    executable runs: its iteration offset is an argument, so every segment
    of that size shares it, the whole run in one segment included. (The
    async event path keys its programs through ``schedule_signature`` and
    leaves ``segment`` unset.)"""
    return (
        "seq",
        _full_config_hash(config),
        float(f_opt),
        dataset_signature(device_data),
        schedule_signature,
        bool(collect_metrics),
        mesh_signature,
        segment,
        _jax_env_signature(),
    )


def batch_cache_key(
    config,
    device_data,
    *,
    R: int,
    t0: int,
    rp_keys,
    sweep_fields,
    collect_metrics: bool = True,
    segment=None,
) -> tuple:
    """Cache key for the replica-batched program (``run_batch``).

    Seeds, swept scalars, fault timelines, Byzantine masks and f* are all
    traced inputs of that program, so they are NOT in the key — which is
    exactly why sweep/seed variants of one structural config hit the same
    cached executable. What remains baked: the structural hash (incl. the
    realized random-topology graph), the UNSWEPT sweepable scalars (closure
    constants when not on the replica axis), the set of per-replica inputs
    the trace was built with (``rp_keys`` — presence changes the input
    pytree), the cohort size R, the continuation offset t0 (timeline
    horizons are t0+T), and the data signature. ``segment`` carries the
    progress-streaming segmentation facts (the per-call trip count
    differs from the one-shot program's).
    """
    sweep_fields = set(sweep_fields)
    unswept = tuple(
        (f, getattr(config, f))
        for f in SWEEPABLE_FIELDS
        if f not in sweep_fields
    )
    return (
        "batch",
        config.structural_hash(),
        int(R),
        int(t0),
        tuple(sorted(rp_keys)),
        unswept,
        dataset_signature(device_data),
        bool(collect_metrics),
        segment,
        _jax_env_signature(),
    )
