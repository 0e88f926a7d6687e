"""``chip_smoke.py`` off the chip: it must refuse to run, and the server it
builds must work.

The smoke itself only ever runs on a TPU (the driver runs it there after
every PR).  What tier-1 can hold is the two things that would otherwise
only break on the chip: that a CPU run is a failure and never a result,
and that the control flow — build the real RAG composition from model
names, serve it threaded, retrieve, answer concurrently, read the ledgers
— works, here with the tiny presets instead of bge-base + Mistral-7B.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent

REHEARSAL = """
import json
import chip_smoke
from pathway_tpu.device import default_executor_snapshot
from pathway_tpu.serving import generation

docs = chip_smoke.make_documents(48, words=(8, 20), long_words=60)
port = chip_smoke.free_port()
server = chip_smoke.build_rag_server(
    "all-MiniLM-L6-v2", "pw-tiny-decoder", docs, port=port, max_new_tokens=4
)
chip_smoke.wait_until_listening(
    port, server.run_server(threaded=True, with_cache=False)
)
run = chip_smoke.drive(port, docs, k=3, n_answers=3, timeout_s=120)
gen = generation.shared_scheduler("pw-tiny-decoder").snapshot()
# 48 rows is under _JAX_MIN_ROWS: the search is host numpy by design
counters = chip_smoke.check_ledgers(
    default_executor_snapshot(), gen, len(run["answers"]), executor_topk=False
)
generation.reset_shared_schedulers()
print(json.dumps({"answers": run["answers"], "hits": [len(h) for h in run["retrieved"]],
                  "tokens": gen["tokens_total"], "counters": counters}))
"""


def _env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_smoke_refuses_to_run_off_the_chip():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, env=_env(), timeout=60,
    )
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""  # a refusal prints no result
    assert time.monotonic() - t0 < 30


def test_verdict_line_has_exactly_the_contract_keys():
    """The driver reads the last stdout line and refuses any other shape:
    ``ok`` and ``device`` = ``platform``/``kind`` (text) and ``count``."""
    import jax

    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    line = chip_smoke.verdict(True, jax.devices())
    assert "\n" not in line
    out = json.loads(line)
    assert list(out) == ["ok", "device"] and out["ok"] is True
    assert list(out["device"]) == ["platform", "kind", "count"]
    assert out["device"] == {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
    assert isinstance(out["device"]["kind"], str)
    assert type(out["device"]["count"]) is int


def test_server_built_by_the_smoke_retrieves_and_answers():
    proc = subprocess.run(
        [sys.executable, "-c", REHEARSAL],
        capture_output=True, text=True, env=_env(), timeout=170,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["hits"] == [3, 3, 3, 3]
    assert len(out["answers"]) == 3 and all(a.strip() for a in out["answers"])
    assert out["tokens"] > 0
    assert set(out["counters"].values()) == {0}
