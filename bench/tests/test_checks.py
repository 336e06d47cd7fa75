"""``correct`` comes out false for the control and for each planted fault.

Each case drives a whole rehearsed run (CPU, tiny widths, Pallas in
interpret mode; the look for a chip is skipped) with the timed path broken
underneath, and reads the result line.  The limits are the ``rehearse``
blocks of the cells' own files (``bench/checks/``), set from CPU readings
at these widths; the chip's limits and their readings are in ``PERF.md``.
Runs take half a minute to two minutes each.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]

CASES = [
    ("vnet-serve-poisson", None, True),
    ("vnet-serve-poisson", "control", False),
    ("vnet-serve-poisson", "fault=altered_answer", False),
    ("dcgan-serve-poisson", None, True),
    ("dcgan-serve-poisson", "control", False),
    ("dcgan-serve-poisson", "fault=altered_answer", False),
]


@pytest.mark.parametrize("workload, check, correct", CASES,
                         ids=[f"{w}-{c}" for w, c, _ in CASES])
def test_correct(workload, check, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "bench/run.py", "--workload", workload, "--seed",
           "2718281828", "--seconds", "1", "--trace", "0", "--rehearse"]
    if check:
        cmd += ["--check", check]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(res)[-1] == "checks"
    assert res["correct"] is correct, res["checks"]
