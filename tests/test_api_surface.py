"""The api-surface check (scripts/check_api_surface.py) as a tier-1 test:
no module outside ``repro/comm`` (and the deprecated shim) may pass raw
``fast_axis=``/``slow_axis=`` kwargs — collectives go through the
``Communicator``.  CI runs the same script in the fast lane."""

import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "scripts"))

import check_api_surface  # noqa: E402


def test_repo_api_surface_is_clean():
    assert check_api_surface.violations(REPO) == []


def test_check_catches_a_violation(tmp_path):
    bad = tmp_path / "src" / "repro" / "runtime"
    bad.mkdir(parents=True)
    (bad / "rogue.py").write_text(
        "from repro.comm import primitives as p\n"
        "def f(x):\n"
        "    return p.naive_all_gather(x, fast_axis='data', "
        "slow_axis='pod')\n")
    hits = check_api_surface.violations(tmp_path)
    assert len(hits) == 1 and "rogue.py:3" in hits[0]
    assert check_api_surface.main([str(tmp_path)]) == 1


def test_check_catches_violation_before_constructor_same_line(tmp_path):
    # models/: outside the ctor-scan paths, so only the kwarg rule fires
    bad = tmp_path / "src" / "repro" / "models"
    bad.mkdir(parents=True)
    (bad / "mixed.py").write_text(
        "y = p.naive_all_gather(x, fast_axis='d'); "
        "c = Communicator(fast_axis='d')\n")
    hits = check_api_surface.violations(tmp_path)
    assert len(hits) == 1 and "mixed.py:1" in hits[0]


def test_check_catches_violation_after_constructor_same_line(tmp_path):
    bad = tmp_path / "src" / "repro" / "models"
    bad.mkdir(parents=True)
    (bad / "trailing.py").write_text(
        "c = Communicator(fast_axis='d'); "
        "y = p.naive_all_gather(x, fast_axis='d')\n")
    hits = check_api_surface.violations(tmp_path)
    assert len(hits) == 1 and "trailing.py:1" in hits[0]


def test_check_allows_constructor_spellings(tmp_path):
    ok = tmp_path / "src" / "repro" / "models"
    ok.mkdir(parents=True)
    (ok / "fine.py").write_text(
        "from repro.comm import Communicator\n"
        "from repro.substrate import VirtualCluster\n"
        "vc = VirtualCluster(pods=2, chips=4, fast_axis=('dp', 'tp'),\n"
        "                    fast_shape=(2, 2), slow_axis='pod')\n"
        "comm = Communicator(fast_axis='data', slow_axis='pod')\n"
        "fast_axis: str = 'data'   # annotated field, not a call kwarg\n")
    assert check_api_surface.violations(tmp_path) == []
    assert check_api_surface.main([str(tmp_path)]) == 0


# ---- bare-Communicator() check on the rebuild paths -------------------------
def test_ctor_caught_in_runtime_and_launch(tmp_path):
    for rel in ("src/repro/runtime", "src/repro/launch"):
        d = tmp_path / rel
        d.mkdir(parents=True)
        (d / "rogue.py").write_text(
            "from repro.comm import Communicator\n"
            "world = Communicator(fast_axis='data', slow_axis='pod')\n")
    hits = check_api_surface.ctor_violations(tmp_path)
    assert len(hits) == 2
    assert all("rogue.py:2" in h for h in hits)
    assert check_api_surface.main([str(tmp_path)]) == 1


def test_ctor_blessed_classmethods_allowed(tmp_path):
    ok = tmp_path / "src" / "repro" / "runtime"
    ok.mkdir(parents=True)
    (ok / "fine.py").write_text(
        "from repro.comm import Communicator\n"
        "world = Communicator.from_cluster(vc)\n"
        "topo_world = Communicator.from_topology(topo)\n"
        "node = world.split_type_shared()\n"
        "# a comment naming Communicator(fast_axis='d') is not a call\n")
    assert check_api_surface.ctor_violations(tmp_path) == []
    assert check_api_surface.main([str(tmp_path)]) == 0


def test_ctor_bare_allowed_outside_rebuild_paths(tmp_path):
    ok = tmp_path / "src" / "repro" / "models"
    ok.mkdir(parents=True)
    (ok / "wrapper.py").write_text(
        "from repro.comm import Communicator\n"
        "tp_comm = Communicator(fast_axis='model')\n")
    assert check_api_surface.ctor_violations(tmp_path) == []


# ---- raw lax.psum / lax.all_gather check ------------------------------------
def test_raw_collective_caught(tmp_path):
    bad = tmp_path / "src" / "repro" / "models"
    bad.mkdir(parents=True)
    (bad / "rogue.py").write_text(
        "from jax import lax\n"
        "def f(x):\n"
        "    return lax.psum(x, 'data')\n"
        "def g(x):\n"
        "    return lax.all_gather(x, 'data', axis=0, tiled=True)\n")
    hits = check_api_surface.raw_violations(tmp_path)
    assert len(hits) == 2
    assert "rogue.py:3" in hits[0] and "rogue.py:5" in hits[1]
    assert check_api_surface.main([str(tmp_path)]) == 1


def test_raw_collective_pragma_allows(tmp_path):
    ok = tmp_path / "src" / "repro" / "models"
    ok.mkdir(parents=True)
    (ok / "fine.py").write_text(
        "from jax import lax\n"
        "from repro.comm.primitives import scoped\n"
        "def f(x):\n"
        "    return scoped(lax.psum, x, 'tp')  # raw-collective: tp path\n")
    assert check_api_surface.raw_violations(tmp_path) == []
    assert check_api_surface.main([str(tmp_path)]) == 0


def test_raw_collective_allowed_paths(tmp_path):
    for rel in ("src/repro/comm", "src/repro/substrate",
                "src/repro/kernels"):
        d = tmp_path / rel
        d.mkdir(parents=True)
        (d / "impl.py").write_text(
            "from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'data')\n")
    assert check_api_surface.raw_violations(tmp_path) == []


def test_raw_collective_commented_call_not_flagged(tmp_path):
    ok = tmp_path / "src" / "repro" / "models"
    ok.mkdir(parents=True)
    (ok / "doc.py").write_text(
        "# the old path used lax.psum(x, 'data') directly\n"
        "def f(x):\n"
        "    return x\n")
    assert check_api_surface.raw_violations(tmp_path) == []


def test_raw_collective_pragma_on_preceding_line_allows(tmp_path):
    ok = tmp_path / "src" / "repro" / "models"
    ok.mkdir(parents=True)
    (ok / "long.py").write_text(
        "from jax import lax\n"
        "def f(x):\n"
        "    # raw-collective: call line too long for an inline pragma\n"
        "    return lax.psum(x, ('pod', 'data', 'model', 'extra_axis'))\n"
        "def g(x):\n"
        "    return lax.psum(x, 'data')   # two lines below the pragma:\n")
    hits = check_api_surface.raw_violations(tmp_path)
    assert len(hits) == 1 and "long.py:6" in hits[0]


# ---- collectives named through primitives.scoped ----------------------------
def test_scoped_collective_caught_without_pragma(tmp_path):
    """Handing the primitive to ``scoped`` outside the comm layers still
    bypasses the Communicator: the raw rule sees it."""
    bad = tmp_path / "src" / "repro" / "runtime"
    bad.mkdir(parents=True)
    (bad / "rogue.py").write_text(
        "from jax import lax\n"
        "from repro.comm.primitives import scoped\n"
        "def f(x):\n"
        "    return scoped(lax.psum, x, 'data')\n")
    hits = check_api_surface.raw_violations(tmp_path)
    assert len(hits) == 1 and "rogue.py:4" in hits[0]


@pytest.mark.parametrize("rel", ["src/repro/comm/impl.py",
                                 "src/repro/core/sync.py",
                                 "src/repro/models/parallel.py",
                                 "src/repro/models/layers.py"])
def test_unscoped_collective_caught(tmp_path, rel):
    """A raw ``lax`` collective call where the program's collectives live
    fails whatever its pragma: the compiled module would not name it."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True)
    path.write_text(
        "from jax import lax\n"
        "def f(x, perm):\n"
        "    return lax.ppermute(x, 'data', perm)  # raw-collective: ring\n"
        "def g(x):\n"
        "    return lax.psum_scatter(x, 'data', tiled=True)\n")
    hits = check_api_surface.unscoped_violations(tmp_path)
    assert len(hits) == 2
    assert "py:3" in hits[0] and "py:5" in hits[1]
    assert check_api_surface.main([str(tmp_path)]) == 1


def test_scoped_collective_and_docstring_mention_pass(tmp_path):
    ok = tmp_path / "src" / "repro" / "comm"
    ok.mkdir(parents=True)
    (ok / "impl.py").write_text(
        "from jax import lax\n"
        "from repro.comm.primitives import scoped\n"
        "def f(x):\n"
        "    \"\"\"Same result as ``lax.psum(x, axes)``.\"\"\"\n"
        "    return scoped(lax.psum, x, ('pod', 'data'))\n")
    assert check_api_surface.unscoped_violations(tmp_path) == []
    assert check_api_surface.main([str(tmp_path)]) == 0


def test_collective_outside_scoped_paths_left_to_raw_rule(tmp_path):
    d = tmp_path / "src" / "repro" / "bench"
    d.mkdir(parents=True)
    (d / "sweep.py").write_text(
        "from jax import lax\n"
        "def f(x):\n"
        "    return lax.psum(x, 'data')  # raw-collective: checksum\n")
    assert check_api_surface.unscoped_violations(tmp_path) == []
    assert check_api_surface.main([str(tmp_path)]) == 0
