"""CLI (spawn/replay/spawn-from-env) and YAML app-loader tests.

Model: the reference launches N identical processes wired into one
cluster via PATHWAY_* env vars (cli.py:53-110) and loads declarative
app.yaml configs whose tags construct pipeline objects
(internals/yaml_loader.py).
"""

import io
import json
import os
import subprocess
import sys
import textwrap

import pytest

import pathway_tpu as pw
from pathway_tpu.internals.yaml_loader import import_object, load_yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cli(args, env_extra=None, cwd=None):
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "pathway_tpu", *args],
        capture_output=True,
        text=True,
        env=env,
        cwd=cwd or REPO,
        timeout=120,
    )


WORKER_SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    out = os.path.join(sys.argv[1], f"out_{os.environ['PATHWAY_PROCESS_ID']}.json")
    with open(out, "w") as f:
        json.dump({
            "process_id": os.environ["PATHWAY_PROCESS_ID"],
            "processes": os.environ["PATHWAY_PROCESSES"],
            "threads": os.environ["PATHWAY_THREADS"],
            "first_port": os.environ["PATHWAY_FIRST_PORT"],
            "run_id": os.environ["PATHWAY_RUN_ID"],
        }, f)
    """
)


def _read_worker_outputs(tmp_path):
    return [
        json.loads(p.read_text()) for p in sorted(tmp_path.glob("out_*.json"))
    ]


def test_spawn_sets_cluster_env(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER_SCRIPT)
    res = _run_cli(
        ["spawn", "-n", "2", "-t", "3", "--first-port", "12345",
         sys.executable, str(script), str(tmp_path)]
    )
    assert res.returncode == 0, res.stderr
    rows = _read_worker_outputs(tmp_path)
    assert {r["process_id"] for r in rows} == {"0", "1"}
    assert all(r["processes"] == "2" and r["threads"] == "3" for r in rows)
    assert all(r["first_port"] == "12345" for r in rows)
    assert len({r["run_id"] for r in rows}) == 1  # one run id for the cluster
    assert "SPMD cluster: 2 process(es)" in res.stderr
    assert "ports 12345..12346" in res.stderr


def test_spawn_pins_each_child_to_its_own_chip(monkeypatch):
    """On a multi-chip host every child would open every chip and all but
    one would fail: child i gets chip i through libtpu's per-process
    environment, unless the caller placed the processes itself."""
    from pathway_tpu import cli

    def env_of(base, processes, process_id):
        return cli._cluster_env(
            base, threads=1, processes=processes, first_port=10000,
            process_id=process_id, run_id="r",
        )

    monkeypatch.setattr(cli, "_local_tpu_chips", lambda: 4)
    for i in range(4):
        env = env_of({}, 4, i)
        assert env["TPU_VISIBLE_CHIPS"] == str(i)
        assert env["TPU_PROCESS_BOUNDS"] == env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert "TPU_VISIBLE_CHIPS" not in env_of({}, 5, 4)  # no fifth chip
    assert "TPU_VISIBLE_CHIPS" not in env_of({}, 1, 0)  # one process: whole host
    assert "TPU_VISIBLE_CHIPS" not in env_of({"JAX_PLATFORMS": "cpu"}, 4, 1)
    placed = env_of({"TPU_VISIBLE_CHIPS": "2,3"}, 4, 1)
    assert placed["TPU_VISIBLE_CHIPS"] == "2,3" and "TPU_PROCESS_BOUNDS" not in placed
    monkeypatch.setattr(cli, "_local_tpu_chips", lambda: 0)
    assert "TPU_VISIBLE_CHIPS" not in env_of({}, 4, 1)  # not a TPU host


def test_spawn_propagates_failure_exit_code(tmp_path):
    script = tmp_path / "boom.py"
    script.write_text("raise SystemExit(3)")
    res = _run_cli(["spawn", sys.executable, str(script)])
    assert res.returncode == 3


def test_replay_sets_replay_env(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, json\n"
        "print(json.dumps({k: os.environ.get(k) for k in"
        " ('PATHWAY_REPLAY_STORAGE','PATHWAY_SNAPSHOT_ACCESS','PATHWAY_PERSISTENCE_MODE')}))\n"
    )
    res = _run_cli(
        ["replay", "--record-path", "rec", "--mode", "speedrun", sys.executable, str(script)]
    )
    assert res.returncode == 0, res.stderr
    env_seen = json.loads(res.stdout.strip())
    assert env_seen["PATHWAY_REPLAY_STORAGE"] == "rec"
    assert env_seen["PATHWAY_SNAPSHOT_ACCESS"] == "replay"
    assert env_seen["PATHWAY_PERSISTENCE_MODE"] == "speedrun"


def test_spawn_from_env(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER_SCRIPT)
    res = _run_cli(
        ["spawn-from-env"],
        env_extra={"PATHWAY_SPAWN_ARGS": f"-n 2 {sys.executable} {script} {tmp_path}"},
    )
    assert res.returncode == 0, res.stderr
    rows = _read_worker_outputs(tmp_path)
    assert {r["process_id"] for r in rows} == {"0", "1"}


def test_airbyte_create_source(tmp_path):
    res = _run_cli(
        ["airbyte", "create-source", "conn", "--image", "airbyte/source-faker:6.2.10"],
        cwd=str(tmp_path),
    )
    assert res.returncode == 0, res.stderr
    text = (tmp_path / "conn.yaml").read_text()
    assert "airbyte/source-faker:6.2.10" in text


RECORD_SCRIPT = textwrap.dedent(
    """
    import sys
    import pathway_tpu as pw

    class S(pw.Schema):
        v: int

    class Source(pw.io.python.ConnectorSubject):
        def run(self):
            for i in (1, 2, 3):
                self.next(v=i)
            self.close()

    live = "--live" in sys.argv
    if live:
        t = pw.io.python.read(Source(), schema=S, name="src")
    else:
        t = pw.io.python.read(
            type("Dead", (pw.io.python.ConnectorSubject,), {"run": lambda self: self.close()})(),
            schema=S,
            name="src",
        )
    pw.io.jsonlines.write(t.select(d=pw.this.v * 2), sys.argv[1])
    pw.run()
    """
)


def test_record_then_replay_round_trip(tmp_path):
    """spawn --record captures the stream; replay re-runs it with NO live
    source (the recording is the whole input)."""
    script = tmp_path / "app.py"
    script.write_text(RECORD_SCRIPT)
    rec = tmp_path / "recording"
    out1, out2 = tmp_path / "o1.jsonl", tmp_path / "o2.jsonl"
    res = _run_cli(
        ["spawn", "--record", "--record-path", str(rec),
         sys.executable, str(script), str(out1), "--live"],
    )
    assert res.returncode == 0, res.stderr
    live = sorted(json.loads(l)["d"] for l in out1.read_text().splitlines())
    assert live == [2, 4, 6]
    # replay: the source emits nothing; rows come from the recording
    res = _run_cli(
        ["replay", "--record-path", str(rec), sys.executable, str(script), str(out2)],
    )
    assert res.returncode == 0, res.stderr
    replayed = sorted(json.loads(l)["d"] for l in out2.read_text().splitlines())
    assert replayed == [2, 4, 6]


# --- YAML loader ------------------------------------------------------------


def test_import_object_forms():
    assert import_object("pw.io.csv") is pw.io.csv
    assert import_object("pathway_tpu.internals.yaml_loader:load_yaml") is load_yaml
    assert import_object("len") is len


def test_load_yaml_constructs_tagged_objects():
    result = load_yaml(
        io.StringIO(
            """
            table: !pw.debug.table_from_markdown
              table_def: |
                a | b
                1 | 2
            """
        )
    )
    assert list(result["table"].column_names()) == ["a", "b"]


def test_load_yaml_variables_and_sharing():
    result = load_yaml(
        io.StringIO(
            """
            $k: 7
            first:
              k: $k
            second:
              k: $k
            shared: !pathway_tpu.internals.yaml_loader:Var
              name: x
            also_shared: $y
            $y: !pathway_tpu.internals.yaml_loader:Var
              name: x
            """
        )
    )
    assert result["first"]["k"] == 7 and result["second"]["k"] == 7
    # a $var definition is constructed once and shared by reference
    assert result["also_shared"].name == "x"


def test_load_yaml_env_fallback(monkeypatch):
    monkeypatch.setenv("MY_YAML_SETTING", "42")
    assert load_yaml(io.StringIO("v: $MY_YAML_SETTING"))["v"] == 42
    with pytest.raises(KeyError):
        load_yaml(io.StringIO("v: $not_defined_lowercase"))


def test_load_yaml_unused_variable_warns():
    with pytest.warns(UserWarning, match="unused YAML variable"):
        load_yaml(io.StringIO("$dead: 1\nlive: 2"))


def test_load_yaml_lexical_scoping():
    # a root definition must not capture an inner subtree's bindings
    with pytest.raises(KeyError, match=r"\$b is not defined"):
        load_yaml(
            io.StringIO(
                """
                $a: $b
                inner:
                  $b: 1
                  v: $a
                """
            )
        )


def test_load_yaml_var_keys_in_tagged_mapping():
    out = load_yaml(
        io.StringIO(
            """
            d: !dict
              $p: 7
              k: $p
            """
        )
    )
    assert out["d"] == {"k": 7}


def test_load_yaml_env_value_constructed_once(monkeypatch):
    monkeypatch.setenv(
        "SHARED_OBJ", "!pathway_tpu.internals.yaml_loader:Var {name: x}"
    )
    out = load_yaml(io.StringIO("a: $SHARED_OBJ\nb: $SHARED_OBJ"))
    assert out["a"] is out["b"]  # one construction, shared by reference


def test_load_yaml_circular_variable_raises(monkeypatch):
    monkeypatch.setenv("LOOPY", "$LOOPY")
    with pytest.raises(ValueError, match="circular"):
        load_yaml(io.StringIO("v: $LOOPY"))
    with pytest.raises(ValueError, match="circular"):
        load_yaml(io.StringIO("$a: $a\nv: $a"))


def test_spawn_signal_death_is_failure(tmp_path):
    script = tmp_path / "sig.py"
    script.write_text("import os, signal; os.kill(os.getpid(), signal.SIGKILL)")
    res = _run_cli(["spawn", sys.executable, str(script)])
    assert res.returncode == 137  # 128 + SIGKILL


def test_spawn_rejects_zero_processes(tmp_path):
    res = _run_cli(["spawn", "-n", "0", sys.executable, "-c", "pass"])
    assert res.returncode != 0
    assert "is not in the range" in res.stderr or "Invalid value" in res.stderr


def test_load_yaml_empty_tag_calls_or_returns():
    out = load_yaml(io.StringIO("d: !dict\ns: !pathway_tpu.internals.yaml_loader:_VAR_TAG"))
    assert out["d"] == {}
    assert out["s"] == "tag:pathway.com,2024:variable"
