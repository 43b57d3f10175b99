"""Launch helpers: mesh axis types, named config presets, compile cache."""
import jax
import pytest
from jax.sharding import AxisType

from repro.configs import get_config
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh


def test_meshes_use_auto_axes():
    """The model code relies on Auto sharding propagation; make_mesh's own
    default (Explicit) breaks the embedding gather."""
    mesh = make_mesh((1,), ("data",))
    assert mesh.axis_types == (AxisType.Auto,)
    mesh2 = make_mesh((1, 1), ("data", "model"))
    assert mesh2.axis_types == (AxisType.Auto, AxisType.Auto)


def test_one_chip_preset_keeps_published_widths():
    full = get_config("h2o-danube-1.8b")
    cut = get_config("h2o-danube-1.8b", preset="one_chip")
    assert cut.n_layers == 4 and full.n_layers == 24
    assert cut.replace(n_layers=full.n_layers) == full
    # a preset wins over the tiny flag
    assert get_config("h2o-danube-1.8b", tiny=True, preset="one_chip") == cut


def test_unknown_preset_raises():
    with pytest.raises(KeyError, match="no preset"):
        get_config("h2o-danube-1.8b", preset="nonesuch")
    with pytest.raises(KeyError, match="no preset"):
        get_config("yi-34b", preset="one_chip")


@pytest.fixture()
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.setup_compile_cache()
    assert got == str(compile_cache.CHECKOUT_CACHE)
    assert compile_cache.CHECKOUT_CACHE.parent.joinpath("chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_env_wins(monkeypatch, restore_cache_dir, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None
