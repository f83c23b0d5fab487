"""chip_smoke.py off the chip: `__main__` refuses anything but a TPU, and
its legs pass their own assertions at TOY size on the CPU — in a fresh
process each, because the entry points run with x64 off (this session
has it on) and because a jit cache warmed under one setting would read
as a retrace under the other."""

import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

LEGS = ("A c2m", "B service", "C joint", "D preempt", "E sweep")


def _run(args, *, devices: int = 1, timeout: float = 600.0):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_ENABLE_X64", "XLA_FLAGS", "NOMAD_TPU_SAN")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_main_refuses_a_cpu_within_seconds_and_builds_nothing():
    t0 = time.monotonic()
    proc = _run(["chip_smoke.py"])
    assert proc.returncode not in (0, None)
    assert time.monotonic() - t0 < 60
    assert "needs a TPU" in proc.stderr
    # no result line, no agent, no leg
    assert proc.stdout.strip() == ""


def test_alone_in_a_directory_exits_nonzero_and_prints_no_result(tmp_path):
    (tmp_path / "chip_smoke.py").write_bytes(
        (REPO / "chip_smoke.py").read_bytes())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode not in (0, None)
    assert "not found next to the script" in proc.stderr
    assert proc.stdout.strip() == ""


class _FakeTPU:
    platform = "tpu"
    device_kind = "TPU v5 lite"


@pytest.mark.parametrize("fails", [False, True])
def test_result_line_has_exactly_the_contract_keys(monkeypatch, capsys, fails):
    """What the driver parses: the last stdout line, keys "ok" and
    "device" and nothing else; a failed leg says ok false and exits 1."""
    import json

    import jax

    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))

    def fake_run(sizes, seed):
        print("[run] wall_s=0 claim=null")
        if fails:
            raise AssertionError("leg failed")
        return {}

    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTPU()])
    monkeypatch.setattr(chip_smoke, "run_smoke", fake_run)
    rc = chip_smoke.main([])
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert set(last) == {"ok", "device"}
    assert last["ok"] is (not fails) and rc == (1 if fails else 0)
    assert last["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1}
    assert type(last["device"]["count"]) is int
    if fails:
        assert "leg failed" in out.err


TOY_RUN = (
    "import json, chip_smoke\n"
    "report = chip_smoke.run_smoke(chip_smoke.TOY, seed=7)\n"
    "print('REPORT ' + json.dumps(report))\n")


@pytest.mark.parametrize("devices", [1, 4])
def test_legs_pass_at_toy_size(devices):
    """One device: the path the one-chip run takes. Four: the solver
    service shards by itself, as on a four-chip host."""
    import json

    proc = _run(["-c", TOY_RUN], devices=devices)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("REPORT ")]
    report = json.loads(line[-1][len("REPORT "):])
    assert report["env"]["x64"] is False
    assert report["env"]["device_count"] == devices
    for leg in LEGS:
        assert report[leg]["ok"] is True, leg
    a = report["A c2m"]
    assert a["placed"] == a["allocs"] and a["retraces"] == 0
    assert report["run"]["claim"] is None
    assert a["twin_failures"] == 0 and a["warm_window_compiles"] == 0
    assert report["D preempt"]["kernel_preempted"] > 0
    assert report["D preempt"]["preempt_solve_launches"] > 0
    if devices > 1:
        assert report["mesh"]["mesh_devices"] == devices
        assert a["sharded"] > 0
        assert set(report["mesh"]["resident_shards"].values()) == {devices}
    else:
        assert "mesh" not in report and a["sharded"] == 0
