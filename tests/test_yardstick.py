"""The repo's one benchmark (``BENCHMARK.json`` + ``benchmarks/``) measures on
the chip or not at all: without a TPU and without ``--rehearse`` a cell stops
before it writes a model file, starts a server or prints a result line, so no
CPU number ever stands under a device metric's name.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from fixtures import REPO, cpu_env

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = {w["name"]: w["chips"] for w in MANIFEST["workloads"]}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_refuses_to_measure_off_the_chip(cell, tmp_path):
    cmd = [sys.executable, os.path.join(REPO, *MANIFEST["command"][1:]),
           "--workload", cell, "--seed", "1", "--seconds", "1"]
    env = dict(cpu_env(1), HOME=str(tmp_path), TMPDIR=str(tmp_path))
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=300, cwd=REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""  # no result line
    assert f"this cell needs {CELLS[cell]} TPU chip(s)" in r.stderr
