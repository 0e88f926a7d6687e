"""The native core's build when several processes start at once in a fresh
checkout (a test run's workers): one of them runs g++, the others wait for
it, and every one of them loads the same .so."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from pathway_tpu import native

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LOAD = """
import sys
from pathway_tpu import native
native._BUILD_DIR = sys.argv[1]
module = native._load()
print(module is not None and module.__file__)
"""


@pytest.mark.skipif(native.get() is None, reason="no native core can be built here")
def test_workers_starting_together_build_one_so_and_each_loads_it(tmp_path):
    build = tmp_path / "build"
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"}
    workers = [
        subprocess.Popen(
            [sys.executable, "-c", LOAD, str(build)], env=env, cwd=ROOT,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for _ in range(4)
    ]
    loaded = []
    for worker in workers:
        out, err = worker.communicate(timeout=300)
        assert worker.returncode == 0, err[-2000:]
        loaded.append(out.strip().splitlines()[-1])
    (so,) = set(loaded)
    assert so.endswith(".so") and os.path.dirname(so) == str(build)
    # nothing half-built is left beside it
    assert sorted(p.suffix for p in build.iterdir()) == [".lock", ".so"]
