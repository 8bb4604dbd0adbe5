"""chip_smoke.py's phase logic on the CPU at a tiny size.

The script refuses to run without an accelerator; these tests steer
that check (and the phase sizes) from here, so the phases, their
checks and the contract's last line are exercised without a chip.
"""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "require_accelerator", jax.devices)
    monkeypatch.setattr(mod, "PHOLD_HOSTS", 16)
    monkeypatch.setattr(mod, "PHOLD_SIM_SECONDS", 1)
    monkeypatch.setattr(mod, "TEST_CLIENTS", 2)
    monkeypatch.setattr(mod, "COMPLETE_CLIENTS", 2)
    return mod


@pytest.mark.parametrize("argv,phases", [
    ([], ["A_phold", "B_test_2_clients", "B_test_2_clients"]),
    (["--chips", "4"], ["phold_4_devices"]),
])
def test_smoke_phases_pass_and_end_with_the_contract_line(
        smoke, capsys, argv, phases):
    assert smoke.main(argv) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    rows = [json.loads(line) for line in lines[:-1]]
    assert [r["phase"] for r in rows] == phases
    assert all(r["events"] > 0 and r["wall_s"] > 0 for r in rows)
    last = json.loads(lines[-1])
    dev = jax.devices()[0]
    assert last == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}


def test_smoke_failed_phase_raises_and_prints_no_result(
        smoke, capsys, monkeypatch):
    # zero simulated seconds: no host receives, so phase A's check fails
    monkeypatch.setattr(smoke, "PHOLD_SIM_SECONDS", 0)
    with pytest.raises(RuntimeError, match="received nothing"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_smoke_without_accelerator_exits_nonzero():
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "no accelerator" in r.stderr
