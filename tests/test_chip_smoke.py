"""``chip_smoke.py`` rehearsed on the CPU, and its refusal to run off a TPU.

The rehearsal option runs the script's real phases — ``DcnnServer`` with a
strict Pallas primary, the Trainer-driven GAN step, the data-parallel step
— at reduced widths in Pallas interpret mode; it is the only way the
script runs without a TPU.
"""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod         # dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(smoke, capsys):
    """No rehearsal option on a CPU backend: non-zero exit naming the
    platform, and no result line."""
    rc = smoke.main([])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in err and "cpu" in err, err
    assert '"ok"' not in out


def test_rehearsal_serves_and_trains(smoke, capsys):
    rc = smoke.main(["--rehearse"])
    out, _ = capsys.readouterr()
    assert rc == 0
    lines = out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}
    assert any(l.startswith("serve dcgan_gen:") for l in lines), out
    assert any(l.startswith("serve vnet:") for l in lines), out
    assert any(l.startswith("train steps") for l in lines), out
    # rehearsals never touch the persistent compile cache
    assert not any(l.startswith("compile cache:") for l in lines), out


def test_serve_phase_checks_against_xla(smoke):
    pallas, xla = smoke._engines(interpret=True)
    errs = smoke.serve_phase(smoke.REHEARSAL, pallas, xla, seed=1)
    assert set(errs) == {"dcgan_gen", "vnet"}
    assert all(e <= smoke.SERVE_REL_ERR for e in errs.values()), errs


def test_serve_phase_fails_on_fallback(smoke):
    """A Pallas engine that cannot plan (a 1-byte VMEM budget under
    strict_vmem) degrades the buckets to XLA — the phase must fail."""
    from repro.core.engine import EngineConfig, UniformEngine
    starved = UniformEngine(EngineConfig(method="pallas", interpret=True,
                                         strict_vmem=True, max_tile_bytes=1))
    _, xla = smoke._engines(interpret=True)
    with pytest.raises(RuntimeError, match="pallas"):
        smoke.serve_phase(smoke.REHEARSAL, starved, xla, seed=0)


def test_rehearsal_four_devices():
    """``--chips 4`` on four virtual CPU devices: the dp step against the
    one-device step, both all-reduce flavours."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"),
                        "--rehearse", "--chips", "4"], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert json.loads(lines[-1])["device"]["count"] == 4
    assert any(l.startswith("dp f32 all-reduce on 4 devices") for l in lines)
    assert any(l.startswith("dp int8 all-reduce on 4 devices")
               for l in lines)
