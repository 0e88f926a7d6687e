"""The one persistent compilation cache (``device/compile_cache.py``).

Placement is the contract: ``JAX_COMPILATION_CACHE_DIR`` set means JAX's
own reading of it stands and no code sets another directory; unset means
``<checkout>/.jax_cache``, the same in every process.  And it must work:
a second process compiling the same function reads the entry the first
one wrote instead of adding one.

conftest switches the cache off for the test process, so each case runs
in a subprocess that switches it back on.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

from pathway_tpu.device import compile_cache

REPO = pathlib.Path(__file__).resolve().parent.parent

PROBE = """
import json, os
import jax, jax.numpy as jnp
from pathway_tpu.device.compile_cache import ensure_compile_cache

calls = []
update = jax.config.update
jax.config.update = lambda name, value: (calls.append(name), update(name, value))
directory = ensure_compile_cache()
jax.config.update = update
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({
    "dir": directory,
    "config": jax.config.jax_compilation_cache_dir,
    "set_in_code": "jax_compilation_cache_dir" in calls,
    "entries": sorted(os.listdir(directory)) if os.path.isdir(directory) else [],
}))
"""


def _probe(**env_overrides: str | None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    for name, value in env_overrides.items():
        if value is None:
            env.pop(name, None)
        else:
            env[name] = value
    proc = subprocess.run(
        [sys.executable, "-c", PROBE],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_placed_cache_is_left_to_jax_and_serves_a_second_process(tmp_path):
    placed = str(tmp_path / "placed")
    first = _probe(JAX_COMPILATION_CACHE_DIR=placed, JAX_ENABLE_COMPILATION_CACHE="true")
    assert first["dir"] == first["config"] == placed
    assert not first["set_in_code"]
    assert first["entries"]  # even a toy program is cached
    second = _probe(JAX_COMPILATION_CACHE_DIR=placed, JAX_ENABLE_COMPILATION_CACHE="true")
    assert second["entries"] == first["entries"]  # read back, nothing added


def test_unplaced_cache_is_the_fixed_in_checkout_path():
    assert compile_cache.DEFAULT_CACHE_DIR == str(REPO / ".jax_cache")
    # a fresh process derives the same path (cache writes stay off here:
    # the test must not fill the checkout's real cache)
    out = _probe(JAX_COMPILATION_CACHE_DIR=None, JAX_ENABLE_COMPILATION_CACHE="false")
    assert out["dir"] == out["config"] == compile_cache.DEFAULT_CACHE_DIR
    assert out["set_in_code"]
