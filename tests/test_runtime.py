"""Process-level set-up every entry point shares (runtime.py): where the
compile cache lives and which device the process got."""

import jax
import pytest

from distributed_optimization_tpu import runtime


@pytest.fixture
def cache_config():
    """Restore the two jax config values configure_compile_cache may set."""
    saved = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])


def test_env_var_set_leaves_jax_config_untouched(monkeypatch, cache_config):
    monkeypatch.setenv(runtime.COMPILE_CACHE_ENV, "/some/dir")
    jax.config.update("jax_compilation_cache_dir", None)
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    assert runtime.configure_compile_cache() == "/some/dir"
    assert calls == []
    assert jax.config.jax_compilation_cache_dir is None


def test_env_var_unset_uses_fixed_checkout_path(monkeypatch, cache_config):
    monkeypatch.delenv(runtime.COMPILE_CACHE_ENV, raising=False)
    first = runtime.configure_compile_cache()
    second = runtime.configure_compile_cache()
    checkout = runtime.DEFAULT_COMPILE_CACHE_DIR.parent
    assert first == second == str(checkout / ".jax_cache")
    assert (checkout / "chip_smoke.py").exists(), "cache sits at the checkout root"
    assert jax.config.jax_compilation_cache_dir == first
    assert (
        jax.config.jax_persistent_cache_min_compile_time_secs
        == runtime.COMPILE_CACHE_MIN_SECONDS
    )


def test_cli_places_the_cache_through_the_helper(monkeypatch):
    """cli.main calls the one helper and has no cache flag of its own."""
    from distributed_optimization_tpu import cli

    calls = []
    monkeypatch.setattr(
        runtime, "configure_compile_cache", lambda: calls.append("cli") or "x"
    )

    class Stop(Exception):
        pass

    def stop(args):  # the cache is placed before any config/run work
        raise Stop

    monkeypatch.setattr(cli, "config_from_args", stop)
    with pytest.raises(Stop):
        cli.main(["--quiet"])
    assert calls == ["cli"]
    with pytest.raises(SystemExit):
        cli.main(["--compile-cache", "/tmp/x"])


def test_device_summary_and_tpu_gate_on_this_cpu_host():
    dev = runtime.device_summary()
    assert dev == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}
    with pytest.raises(SystemExit, match="needs a TPU.*'cpu'"):
        runtime.require_tpu("test")


def test_unknown_device_has_no_peaks():
    assert runtime.device_peaks("TPU v5 lite")["bf16_tflops"] == 197.0
    with pytest.raises(SystemExit, match="no published peaks"):
        runtime.device_peaks("cpu")
