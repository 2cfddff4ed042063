"""Launcher plumbing: the compile-cache rule, parameter creation, and the
engine importing without the lint package."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.launch.serve import init_params, use_compile_cache
from repro.models import build_model

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_wins(monkeypatch, restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None  # JAX reads the env


def test_compile_cache_defaults_to_repo_root(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = use_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert use_compile_cache() == path  # fixed: the same on every call


def test_init_params_keeps_configured_dtype():
    model = build_model(get_smoke_config("stablelm-1.6b"))
    params = init_params(model, seed=0)
    dtypes = {a.dtype for a in jax.tree_util.tree_leaves(params)}
    assert dtypes == {jnp.dtype(model.cfg.param_dtype)}
    again = init_params(model, seed=0)
    assert all(
        bool((a == b).all())
        for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(again))
    )


def test_engine_imports_without_the_lint_package():
    code = (
        "import sys; import repro.serving; "
        "assert 'repro.analysis' not in sys.modules, sorted("
        "m for m in sys.modules if m.startswith('repro.analysis'))"
    )
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    subprocess.run([sys.executable, "-c", code], check=True, env=env, timeout=120)


def test_chip_smoke_refuses_without_a_tpu():
    """No accelerator: non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_mesh_server_keeps_one_copy_of_the_weights():
    """On a serving mesh the first slice's placement is the stage slices
    themselves, and weights handed straight to the server leave no other
    copy: the device holds exactly the server's arrays."""
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import PipelineServer

    def device_bytes():
        seen = {}
        for a in jax.live_arrays():
            for s in a.addressable_shards:
                seen[s.data.unsafe_buffer_pointer()] = s.data.nbytes
        return sum(seen.values())

    model = build_model(get_smoke_config("stablelm-1.6b"))
    mesh = make_serving_mesh(model_axis=1, data_axis=1)
    before = device_bytes()
    server = PipelineServer(
        model, init_params(model, 0, mesh), mesh=mesh, n_groups=3,
        n_replicas=2, max_batch=2, max_len=32, harvest_bounds=(60.0, 80.0),
    )
    held = {}
    for g, (_, stage) in enumerate(server.stages):
        placed = server._placed_params[(g, 0)]
        for a, b in zip(jax.tree_util.tree_leaves(stage), jax.tree_util.tree_leaves(placed)):
            assert a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()
            held[a.unsafe_buffer_pointer()] = a.nbytes
    caches = {
        s.data.unsafe_buffer_pointer(): s.data.nbytes
        for c in jax.tree_util.tree_leaves(server.__dict__)
        if isinstance(c, jax.Array)
        for s in c.addressable_shards
    }
    assert device_bytes() - before == sum({**caches, **held}.values())
