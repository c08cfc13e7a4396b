"""Persistent executable store (ISSUE-15 tentpole): restart-warm loads,
provenance guards, and the corruption-degrades-to-cold-compile contract
(``serving/store.py``)."""

from __future__ import annotations

import hashlib
import os
import pickle

import numpy as np
import pytest

from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.serving.cache import ExecutableCache
from distributed_optimization_tpu.serving.store import (
    ARTIFACT_SUFFIX,
    STORE_SCHEMA_VERSION,
    PersistentExecutableStore,
    key_digest,
    process_executable_store,
    process_store_root,
    store_provenance,
)

def _store_warnings(capsys, needle: str) -> list[str]:
    """The store logs through the package's own stderr handler (no
    propagation), so warnings are counted from captured stderr."""
    err = capsys.readouterr().err
    return [ln for ln in err.splitlines()
            if "[store]" in ln and needle in ln]


def _cfg(**over):
    fields = dict(
        n_workers=4, n_samples=120, n_features=6, n_informative_features=4,
        problem_type="quadratic", n_iterations=40, eval_every=10,
        local_batch_size=8, dtype="float64",
    )
    fields.update(over)
    return ExperimentConfig(**fields)


def _run(cfg, cache):
    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.utils.data import (
        generate_synthetic_dataset,
    )
    from distributed_optimization_tpu.utils.oracle import (
        compute_reference_optimum,
    )

    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(
        ds, cfg.reg_param, huber_delta=cfg.huber_delta,
        n_classes=cfg.n_classes,
    )
    return jax_backend.run(cfg, ds, f_opt, executable_cache=cache)


def _artifacts(root) -> list:
    return sorted(
        os.path.join(str(root), n)
        for n in os.listdir(str(root)) if n.endswith(ARTIFACT_SUFFIX)
    )


# --------------------------------------------------- the restart-warm gate


def test_store_restart_warm_bitwise_then_corruption_degrades(
    tmp_path, capsys
):
    """The full lifecycle the tentpole promises: a cold compile writes
    through to disk; a FRESH cache over the same directory (a process
    restart) serves the program with 0 compile seconds and bitwise the
    cold result; a truncated artifact then degrades to a cold compile
    with one warning, never a crash."""
    cfg = _cfg()

    # --- cold: compile + write-through --------------------------------
    store_a = PersistentExecutableStore(tmp_path)
    cache_a = ExecutableCache(store=store_a)
    cold = _run(cfg, cache_a)
    assert cold.history.compile_seconds > 0.0
    assert store_a.stats()["saves"] >= 1
    paths = _artifacts(tmp_path)
    assert len(paths) >= 1
    assert store_a.stats()["disk_bytes"] > 0

    # --- restart: fresh cache, fresh store instance, same directory ---
    cache_b = ExecutableCache(store=PersistentExecutableStore(tmp_path))
    warm = _run(cfg, cache_b)
    assert warm.history.compile_seconds == 0.0
    assert np.array_equal(warm.history.objective, cold.history.objective)
    assert np.array_equal(warm.final_models, cold.final_models)
    assert np.array_equal(warm.final_avg_model, cold.final_avg_model)
    st = cache_b.stats()
    assert st["store_hits"] == 1
    assert st["store"]["load_hits"] == 1
    assert st["store"]["load_seconds"] > 0.0
    assert st["compile_seconds_saved"] > 0.0

    # --- corruption: truncate the artifact mid-byte -------------------
    with open(paths[0], "r+b") as f:
        f.truncate(max(1, os.path.getsize(paths[0]) // 3))
    cache_c = ExecutableCache(store=PersistentExecutableStore(tmp_path))
    capsys.readouterr()  # drain anything earlier phases printed
    recovered = _run(cfg, cache_c)
    # Degraded, not dead: a cold compile with the bitwise-same result.
    assert recovered.history.compile_seconds > 0.0
    assert np.array_equal(
        recovered.history.objective, cold.history.objective
    )
    st = cache_c.stats()["store"]
    assert st["corrupt"] >= 1 and st["load_hits"] == 0
    warned = _store_warnings(capsys, "corrupt/unreadable")
    assert len(warned) == 1  # one warning per artifact, not per lookup
    assert "cold compile" in warned[0]
    # The recompile wrote a REPLACEMENT artifact over the corpse, so the
    # next restart is warm again.
    cache_d = ExecutableCache(store=PersistentExecutableStore(tmp_path))
    rewarmed = _run(cfg, cache_d)
    assert rewarmed.history.compile_seconds == 0.0


# ------------------------------------------------------ provenance guards


def _fake_artifact(store, key, **overrides):
    record = {
        "schema": STORE_SCHEMA_VERSION,
        "provenance": store_provenance(),
        "key_repr": repr(key),
        "payload": b"not-an-executable",
        "in_tree": None,
        "out_tree": None,
        "cost": None,
        "compile_seconds": 1.0,
    }
    record.update(overrides)
    path = store._path(key)
    with open(path, "wb") as f:
        f.write(pickle.dumps(record))
    return path


def test_wrong_jax_version_artifact_skipped(tmp_path, capsys):
    """An artifact from another jax version is skipped with one warning
    (serialized XLA executables are not portable across versions) — it
    must never reach the deserializer."""
    store = PersistentExecutableStore(tmp_path)
    key = ("seq", "some-hash")
    prov = dict(store_provenance())
    prov["jax_version"] = "0.0.0-from-the-past"
    _fake_artifact(store, key, provenance=prov)
    capsys.readouterr()
    assert store.load(key) is None
    assert store.load(key) is None
    st = store.stats()
    assert st["skipped_provenance"] == 2
    assert st["corrupt"] == 0  # the guard fired BEFORE deserialization
    assert st["load_hits"] == 0
    warned = _store_warnings(capsys, "provenance mismatch")
    assert len(warned) == 1  # one warning per artifact
    assert "0.0.0-from-the-past" in warned[0]


def test_wrong_device_kind_and_x64_skipped(tmp_path):
    store = PersistentExecutableStore(tmp_path)
    key = ("batch", "h")
    prov = dict(store_provenance())
    prov["device_kind"] = "TPU v9000"
    _fake_artifact(store, key, provenance=prov)
    assert store.load(key) is None
    prov = dict(store_provenance())
    prov["x64"] = not prov["x64"]
    _fake_artifact(store, key, provenance=prov)
    assert store.load(key) is None
    assert store.stats()["skipped_provenance"] == 2


def test_artifact_over_devices_the_process_lacks_is_skipped(tmp_path, capsys):
    """An artifact made over more devices than this process sees (or by a
    store that recorded none) is a provenance miss before the deserializer,
    never a program bound to the wrong devices."""
    import jax

    store = PersistentExecutableStore(tmp_path)
    key = ("seq", "wide-mesh")
    _fake_artifact(store, key, device_ids=[0, len(jax.devices())])
    capsys.readouterr()
    assert store.load(key) is None
    _fake_artifact(store, key)
    assert store.load(key) is None
    st = store.stats()
    assert (st["skipped_provenance"], st["corrupt"]) == (2, 0)
    assert len(_store_warnings(capsys, "device_ids")) == 1


def test_key_repr_mismatch_reads_as_corrupt(tmp_path):
    """A digest collision / key-format drift is caught by the stored
    key repr and reads as a miss, never as the wrong program."""
    store = PersistentExecutableStore(tmp_path)
    key = ("seq", "real-key")
    _fake_artifact(store, key, key_repr=repr(("seq", "OTHER-key")))
    assert store.load(key) is None
    assert store.stats()["corrupt"] == 1


def test_unknown_schema_reads_as_corrupt(tmp_path):
    store = PersistentExecutableStore(tmp_path)
    key = ("seq", "k")
    _fake_artifact(store, key, schema=STORE_SCHEMA_VERSION + 1)
    assert store.load(key) is None
    assert store.stats()["corrupt"] == 1


def test_missing_artifact_is_a_quiet_miss(tmp_path, capsys):
    store = PersistentExecutableStore(tmp_path)
    capsys.readouterr()
    assert store.load(("never", "saved")) is None
    assert store.stats()["load_misses"] == 1
    # Absence is normal, not warning-worthy.
    assert _store_warnings(capsys, "") == []


def test_save_failure_degrades_to_warning(tmp_path, capsys):
    """An unserializable executable warns once and returns False — the
    request that just compiled successfully must not fail."""
    from distributed_optimization_tpu.serving.cache import CacheEntry

    store = PersistentExecutableStore(tmp_path)
    entry = CacheEntry(
        executable=object(), cost=None, compile_seconds=1.0, est_bytes=1,
    )
    capsys.readouterr()
    assert store.save(("k",), entry) is False
    assert store.save(("k",), entry) is False
    st = store.stats()
    assert st["save_errors"] == 2 and st["saves"] == 0
    assert _artifacts(tmp_path) == []  # no half-written file left behind
    assert len(_store_warnings(capsys, "could not persist")) == 1


# ----------------------------------------------------------- naming + env


def test_key_digest_is_stable_sha256_of_repr():
    key = ("seq", "abc", 1.5, (True, None))
    assert key_digest(key) == hashlib.sha256(repr(key).encode()).hexdigest()
    assert key_digest(key) == key_digest(("seq", "abc", 1.5, (True, None)))
    assert key_digest(key) != key_digest(("seq", "abc", 1.5, (True, False)))


def test_process_store_env_wiring(tmp_path, monkeypatch):
    """``DOPT_EXEC_STORE`` names the process store (how spawned workers
    inherit the shared warm tier); unset/blank means no store."""
    monkeypatch.delenv("DOPT_EXEC_STORE", raising=False)
    assert process_store_root() is None
    assert process_executable_store() is None
    root_a = tmp_path / "a"
    monkeypatch.setenv("DOPT_EXEC_STORE", str(root_a))
    store = process_executable_store()
    assert store is not None and store.root == str(root_a)
    assert process_executable_store() is store  # one instance per root
    # Re-pointing the env var (tests only) builds a fresh instance.
    root_b = tmp_path / "b"
    monkeypatch.setenv("DOPT_EXEC_STORE", str(root_b))
    assert process_executable_store().root == str(root_b)


def test_store_stats_shape_is_json_safe(tmp_path):
    import json

    st = PersistentExecutableStore(tmp_path).stats()
    json.dumps(st)  # every value is a plain scalar/string
    for k in ("saves", "save_errors", "load_hits", "load_misses",
              "skipped_provenance", "corrupt", "load_seconds", "root",
              "artifacts", "disk_bytes"):
        assert k in st
