"""The one backend bootstrap (tensor/backend.py): where the compile
cache goes, which device a tpu-* algorithm may run on, and that every
surface names the device it resolved."""

import json
import os
import subprocess
import sys
import urllib.request
from pathlib import Path

import jax
import pytest

from nomad_tpu.tensor import backend

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls without applying them: a cache
    directory set for real would outlive the test."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    return calls


@pytest.fixture
def silent_cpu():
    """JAX on the CPU although nobody asked for it: what a chip that
    failed to initialise looks like from inside the process."""
    jax.devices()
    jax.config.update("jax_platforms", None)
    try:
        yield
    finally:
        jax.config.update("jax_platforms", "cpu")


def test_cache_dir_from_the_environment_is_left_to_jax(
        monkeypatch, tmp_path, config_updates):
    monkeypatch.setenv(backend.CACHE_ENV, str(tmp_path))
    assert backend.cache_dir() == str(tmp_path)
    backend.bootstrap()
    assert not [k for k, _ in config_updates if k.endswith("cache_dir")]


def test_cache_dir_defaults_to_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv(backend.CACHE_ENV, raising=False)
    want = str(REPO / ".jax_cache")
    assert backend.cache_dir() == want
    backend.bootstrap()
    assert [v for k, v in config_updates if k.endswith("cache_dir")] == [want]


def test_cache_entries_land_where_the_environment_says(tmp_path):
    """A fresh process with the variable set: entries appear there, and
    none under the checkout's default directory."""
    default = REPO / ".jax_cache"
    before = set(os.listdir(default)) if default.exists() else set()
    code = (
        "import jax, numpy as np\n"
        "from nomad_tpu.tensor.backend import bootstrap\n"
        "bootstrap()\n"
        # the test is about the place, not the threshold
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert os.listdir(tmp_path / "cache")
    after = set(os.listdir(default)) if default.exists() else set()
    assert after == before


def test_device_names_what_jax_reports():
    dev = backend.device()
    first = jax.devices()[0]
    assert dev.as_dict() == {"platform": first.platform,
                             "kind": first.device_kind,
                             "count": len(jax.devices())}


def test_explicit_cpu_is_admitted():
    assert jax.config.jax_platforms == "cpu"    # conftest named it
    assert backend.require_tpu().platform == "cpu"


def test_silent_cpu_is_refused(silent_cpu, config_updates):
    from nomad_tpu.structs import enums
    from nomad_tpu.tensor.placer import TPUPlacer

    with pytest.raises(backend.BackendError, match="needs a TPU"):
        backend.require_tpu()
    with pytest.raises(backend.BackendError):
        backend.bootstrap(enums.SCHED_ALG_TPU_SOLVE)
    # a host algorithm never needed the chip
    assert backend.bootstrap(enums.SCHED_ALG_BINPACK).platform == "cpu"
    # the factory every tpu-* placement goes through: an operator
    # flipping the algorithm on a running agent cannot reach it either
    with pytest.raises(backend.BackendError):
        TPUPlacer()


def test_tpu_agent_refuses_to_start_on_a_silent_cpu(
        silent_cpu, config_updates, capsys):
    from nomad_tpu import cli

    rc = cli.main(["agent", "--algorithm", "tpu-binpack", "--clients", "0",
                   "--port", "0"])
    assert rc == 1
    err = capsys.readouterr().err
    assert "agent failed to start" in err and "needs a TPU" in err


def test_agent_names_the_device(config_updates):
    """On the start line and under /v1/agent/self, next to the solver
    service's stats."""
    from nomad_tpu import cli

    args = cli.build_parser().parse_args(
        ["agent", "--algorithm", "tpu-binpack", "--clients", "0",
         "--port", "0", "--workers", "1"])
    agent = cli.Agent(args)
    try:
        dev = backend.device()
        assert f"device={dev}" in agent.start_line
        with urllib.request.urlopen(agent.http.address + "/v1/agent/self",
                                    timeout=10) as r:
            stats = json.loads(r.read())["stats"]
        assert stats["device"] == dev.as_dict()
        assert {"launches", "retraces", "twin_failures"} <= set(stats["solver"])
    finally:
        agent.stop()


# -- failures surface ------------------------------------------------------


def test_cache_size_raises_without_the_probe():
    """A callable no_retrace cannot count compiles for must not pass
    for one that compiled nothing."""
    from nomad_tpu.tensor.jit_guard import cache_size, no_retrace

    warm = jax.jit(lambda x: x + 1)
    warm(1.0)
    assert cache_size(warm) == 1
    with pytest.raises(TypeError, match="_cache_size"):
        cache_size(lambda x: x)
    with pytest.raises(TypeError):
        with no_retrace(lambda x: x):
            pass


def test_device_twin_failure_is_counted_and_repaired(caplog):
    """A scatter that breaks on the device falls back to the exact host
    rebuild, but shows: in stats, in the Registry and in the log."""
    import numpy as np

    from nomad_tpu.core.metrics import REGISTRY
    from nomad_tpu.tensor.solver import BulkSolverService

    svc = BulkSolverService()
    base = np.full((8, 4), 5.0, np.float32)

    class Req:
        @staticmethod
        def used_dev_fn(mesh):
            raise RuntimeError("scatter broke on the device")

        @staticmethod
        def used_fn():
            return base

    before = REGISTRY.get("nomad.solver.twin_failures")
    with caplog.at_level("ERROR", logger="nomad_tpu.solver"):
        out = svc._resync_base(Req, static=None, mesh=None, d=4,
                               ledger_entries=[])
    assert np.array_equal(np.asarray(out), base)        # repaired, exact
    assert svc.stats["twin_failures"] == 1
    assert REGISTRY.get("nomad.solver.twin_failures") == before + 1
    assert "scatter broke on the device" in caplog.text

    # a feed that cannot serve the static is a miss, not a failure
    Req.used_dev_fn = staticmethod(lambda mesh: None)
    svc._resync_base(Req, static=None, mesh=None, d=4, ledger_entries=[])
    assert svc.stats["twin_failures"] == 1
