"""utils/backend.py: where the compile cache goes, and the device report."""

import os

import jax
import pytest

from distributed_ba3c_tpu.utils import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    keyed = getattr(jax.config, METADATA_IN_KEY)
    yield lambda: jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update(METADATA_IN_KEY, keyed)


def test_cache_dir_from_outside_is_left_alone(monkeypatch, cache_config):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself, and the helper
    sets no path in code."""
    before = cache_config()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert backend.configure_compile_cache() == "/somewhere/else"
    assert cache_config() == before


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout(
    monkeypatch, cache_config
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # the chip machine's
    want = os.path.join(REPO, ".jax_cache")
    assert backend.configure_compile_cache() == want
    assert cache_config() == want
    # fixed: the path is part of the cache key
    assert backend.configure_compile_cache() == want


def test_cpu_only_process_gets_no_cache(monkeypatch, cache_config):
    before = cache_config()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert backend.configure_compile_cache() is None
    assert cache_config() == before


@pytest.mark.parametrize("placed", [None, "/somewhere/else"])
def test_a_cache_in_use_is_keyed_on_the_programs_metadata(
        monkeypatch, cache_config, placed):
    """A program read from the cache keeps the op names it was compiled
    with; JAX's default key ignores them, and a capture would then show
    another build's scopes (utils/profiling.py), or none."""
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    jax.config.update(METADATA_IN_KEY, False)
    backend.configure_compile_cache()
    assert getattr(jax.config, METADATA_IN_KEY) is True


@pytest.mark.parametrize("platforms,expected", [
    ("cpu", True), (" cpu ,cpu", True),
    ("tpu,cpu", False), ("tpu", False), ("", False), (None, False),
])
def test_cpu_only(monkeypatch, platforms, expected):
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    assert backend.cpu_only() is expected


def test_device_info_is_what_jax_reports():
    d = jax.devices()
    assert backend.device_info() == {
        "platform": d[0].platform, "kind": d[0].device_kind, "count": len(d),
    }


def test_stat_holder_names_the_device_in_its_first_record_only(tmp_path):
    import json

    from distributed_ba3c_tpu.utils.stats import StatHolder

    info = {"device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    holder = StatHolder(str(tmp_path), tensorboard=False, run_info=info)
    holder.add_stat("loss", 1.0)
    assert holder.finalize() == {"loss": 1.0}  # scalars only to TB/printers
    holder.add_stat("loss", 2.0)
    holder.finalize()
    with open(tmp_path / "stat.json") as f:
        assert json.load(f) == [{"loss": 1.0, **info}, {"loss": 2.0}]
