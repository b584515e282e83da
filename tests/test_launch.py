"""The training and serving entry points, end to end at ``--reduced`` size,
plus the compile-cache placement they share."""

import math

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.launch import compile_cache
from repro.launch import serve as launch_serve
from repro.launch import train as launch_train
from repro.launch.mesh import make_mesh_from_topo
from repro.core.topology import MeshTopology
from repro.runtime.steps import make_train_step


def test_train_launcher_end_to_end(monkeypatch):
    cache_calls = []
    monkeypatch.setattr(launch_train, "enable_compile_cache",
                        lambda: cache_calls.append(True))
    rep = launch_train.main(["--arch", "qwen3-0.6b", "--reduced",
                             "--steps", "4", "--batch", "8", "--seq", "16"])
    assert cache_calls == [True]
    assert len(rep.losses) == 4 and len(rep.step_times) == 4
    assert all(math.isfinite(x) for x in rep.losses)
    assert rep.losses[-1] < rep.losses[0]


def test_serve_launcher_end_to_end(monkeypatch):
    cache_calls, built = [], []
    monkeypatch.setattr(launch_serve, "enable_compile_cache",
                        lambda: cache_calls.append(True))
    real_build = launch_serve.build_by_name

    def build(name, **kw):
        model = real_build(name, **kw)
        built.append(model.cfg)
        return model

    monkeypatch.setattr(launch_serve, "build_by_name", build)
    results = launch_serve.main(["--arch", "qwen3-0.6b", "--reduced",
                                 "--requests", "5", "--slots", "2",
                                 "--prompt-max", "8", "--max-new", "3"])
    assert cache_calls == [True]
    assert built[0].d_model == 64 and built[0].n_layers == 2
    assert sorted(results) == list(range(5))
    for r in results.values():
        assert r.tokens.shape == (1, 3)
        assert np.all((r.tokens >= 0) & (r.tokens < built[0].vocab))


def test_serve_launcher_defaults_to_published_widths(monkeypatch):
    """Without ``--reduced`` the served model is the published config."""
    class Built(Exception):
        pass

    def build(name, *, reduced, **_):
        raise Built(name, reduced)

    monkeypatch.setattr(launch_serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(launch_serve, "build_by_name", build)
    with pytest.raises(Built) as got:
        launch_serve.main(["--arch", "qwen3-0.6b"])
    assert got.value.args == ("qwen3-0.6b", False)


def test_compile_cache_dir_prefers_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: updates.append(a))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []            # jax reads the variable itself


def test_compile_cache_dir_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: updates.append(a))
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (compile_cache.DEFAULT_DIR.parent / "pyproject.toml").is_file()
    assert updates == [("jax_compilation_cache_dir", path)]


def test_init_state_is_built_sharded():
    """Each device materializes only its shard of the train state: nothing
    is built whole on one device and moved afterwards."""
    topo = MeshTopology({"data": 4, "model": 2}, slow_axes=())
    if jax.device_count() < topo.num_devices:
        pytest.skip(f"needs {topo.num_devices} devices")
    mesh = make_mesh_from_topo(topo)
    cfg = get_config("qwen3-0.6b").reduced()
    bundle = make_train_step(cfg, topo, mesh, mode="hier")
    state = bundle.init_state(0)
    specs = jax.tree.leaves(bundle.state_specs,
                            is_leaf=lambda x: isinstance(x, P))
    leaves = jax.tree.leaves(state)
    assert len(specs) == len(leaves)
    sharded = 0
    for leaf, spec in zip(leaves, specs):
        want = NamedSharding(mesh, spec)
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
        shard = leaf.addressable_shards[0].data
        assert shard.shape == want.shard_shape(leaf.shape)
        sharded += shard.size < leaf.size
    assert sharded > 0
