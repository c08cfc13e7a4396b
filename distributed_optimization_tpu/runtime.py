"""Process-level JAX set-up shared by every entry point.

Two facts an entry point settles before it does any work: where XLA's
persistent compile cache lives, and which device the process actually got.
``cli.main``, the serving daemon's ``main``, ``bench.py`` and
``chip_smoke.py`` all call these, so there is one answer to each.

Nothing here swallows an exception: a device that cannot be read is an
error for a measurement path (``telemetry.provenance`` keeps its
best-effort ``None`` for manifest sidecars only).
"""

from __future__ import annotations

import os
from pathlib import Path

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# The cache directory is part of every cache key, so it is a fixed path
# inside the checkout — never a temporary, per-process or per-run one.
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"
# One threshold for every entry point: programs that compile faster than
# this are cheaper to rebuild than to serialize and reload.
COMPILE_CACHE_MIN_SECONDS = 0.5


def configure_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in use.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, whoever launched the process
    owns the cache: JAX reads the variable itself and nothing is set here.
    Otherwise the cache is ``<checkout>/.jax_cache``. Call before the
    first compile.
    """
    from_env = os.environ.get(COMPILE_CACHE_ENV)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE_DIR))
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", COMPILE_CACHE_MIN_SECONDS
    )
    return str(DEFAULT_COMPILE_CACHE_DIR)


def device_summary() -> dict:
    """The default device as JAX reports it: platform, kind and count."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_tpu(what: str) -> dict:
    """``device_summary()`` if the default backend is a TPU, else exit.

    A measurement path that finds no chip fails; it does not publish CPU
    numbers under a device metric's name.
    """
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"{what}: needs a TPU, but jax.default_backend() is {backend!r} "
            f"({len(jax.devices())} device(s) visible) — refusing to run"
        )
    return device_summary()


# Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
}


def device_peaks(device_kind: str) -> dict:
    """Peak FLOP/s and HBM bandwidth of ``device_kind``; a device that is
    not in the table is an error, not a default — a utilization against
    another chip's peak is a wrong number."""
    if device_kind not in DEVICE_PEAKS:
        raise SystemExit(
            f"no published peaks recorded for device kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add it to "
            "runtime.DEVICE_PEAKS with its source before reporting a "
            "utilization"
        )
    return DEVICE_PEAKS[device_kind]
