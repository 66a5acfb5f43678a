"""CPU rehearsal of ``chip_smoke.py``: its plain, spiking+Phi and four-chip
phases at the smoke config (Pallas kernels in interpret mode; four virtual
CPU devices), and its refusal to run without a TPU."""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def plain(smoke):
    return smoke.phase_plain(smoke=True)


def test_plain_phase_paged_matches_dense(smoke, plain):
    assert sorted(plain["tokens"]) == list(range(smoke.N_REQUESTS))
    assert all(len(t) == smoke.MAX_NEW for t in plain["tokens"].values())
    assert plain["dlogit_dense"] == 0.0
    assert set(plain["times"]) >= {"init", "compile", "serve"}


def test_phi_phase_prefill_pallas_and_coo_exact(smoke, plain, capsys):
    phi = smoke.phase_phi(smoke=True, params=plain["params"])
    assert sorted(phi["tokens"]) == list(range(smoke.N_REQUESTS))
    assert phi["dlogit_coo"] <= smoke.COO_LOGIT_BOUND
    assert set(phi["times"]) >= {"calibration", "compile", "serve"}
    out = capsys.readouterr().out
    prefill = [ln for ln in out.splitlines() if "dispatch prefill" in ln]
    assert prefill and all(
        any(f"-> {impl} " in ln for impl in smoke.PALLAS_IMPLS)
        for ln in prefill), out


def test_main_refuses_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "no TPU" in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_four_chip_phase_on_virtual_devices():
    """The sharded Phi serve path on a (1, 4) mesh of virtual CPU devices
    (a child process, so the device-count flag stays out of this one)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"    # never reach for a chip this process holds
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke; "
         "r = chip_smoke.phase_four_chips(smoke=True); "
         "print('DLOGIT', r['dlogit'])"],
        cwd=_PATH.parent, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "DLOGIT 0.0" in out.stdout, out.stdout[-4000:]
    assert "shards=4" in out.stdout
