"""Test configuration: force a deterministic 8-device CPU mesh.

Tests run on the CPU backend by design: multi-chip sharding tests use
virtual CPU devices (xla_force_host_platform_device_count), the same
trick ``dryrun_multichip`` uses.  The chip is exercised by
``chip_smoke.py``, never by pytest.

The persistent compilation cache (``pathway_tpu/device/compile_cache.py``)
is switched off here: tests count real backend compiles
(``jax.compile.count``), which a warm cache from an earlier run would
turn into cache reads.  The cache helper's own tests enable it in
subprocesses with a directory of their own.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402

# the lint golden corpus holds deliberately-broken snippets (syntax
# errors, fake chaos test files) for tests/test_static_analysis.py —
# they are lint INPUT, never importable test modules
collect_ignore_glob = ["lint_corpus/*"]


@pytest.fixture(autouse=True)
def clear_graph():
    """Each test gets a fresh global graph (reference tests do G.clear())."""
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    yield
    G.clear()
