"""Process-orchestration CLI.

Parity target: ``python/pathway/cli.py`` — ``spawn`` forks N identical
processes of the user's script with ``PATHWAY_THREADS/PROCESSES/
FIRST_PORT/PROCESS_ID/RUN_ID`` set (every worker builds the same dataflow
and owns a shard, SURVEY.md §2b); ``replay`` re-runs a script against a
recorded input stream; ``spawn-from-env`` re-execs ``spawn`` with
arguments taken from ``PATHWAY_SPAWN_ARGS`` (the k8s-operator hook).

TPU mapping: one spawned process per TPU host (the reference maps one per
CPU socket); in-process workers become mesh axes, so ``--threads`` is
accepted for parity but the device mesh is what actually scales compute.
"""

from __future__ import annotations

import glob
import os
import secrets
import subprocess
import sys
import uuid
from typing import Any, NoReturn

import click

import pathway_tpu as pw


# libtpu's per-process environment: which of the host's chips a process
# opens, and the (trivial) topology it then forms by itself
_TPU_PROCESS_ENV = (
    "TPU_VISIBLE_CHIPS",
    "TPU_VISIBLE_DEVICES",
    "TPU_CHIPS_PER_PROCESS_BOUNDS",
    "TPU_PROCESS_BOUNDS",
)


def _local_tpu_chips() -> int:
    """TPU chips on this host, counted from their device nodes — the
    launcher must not ask JAX: a parent that has initialised a backend
    holds the chips its children need."""
    return len(glob.glob("/dev/accel[0-9]*")) or len(glob.glob("/dev/vfio/[0-9]*"))


def _chip_env(env_base: dict[str, str], processes: int, process_id: int) -> dict[str, str]:
    """One chip per spawned process on a multi-chip host.

    A chip belongs to one process at a time, and by default every process
    opens every chip of its host: with the parent's environment passed on
    unchanged, all but one child of ``spawn -n K`` fail or hang at backend
    start-up.  Child ``i`` is therefore pinned to chip ``i`` — unless the
    caller already set any of libtpu's per-process variables, runs on CPU,
    spawns a single process (which may want the whole host), or the host
    has no chip ``i``."""
    if (
        processes < 2
        or env_base.get("JAX_PLATFORMS", "").lower() == "cpu"
        or any(name in env_base for name in _TPU_PROCESS_ENV)
        or process_id >= _local_tpu_chips()
    ):
        return {}
    return {
        "TPU_VISIBLE_CHIPS": str(process_id),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def _cluster_env(
    env_base: dict[str, str],
    *,
    threads: int,
    processes: int,
    first_port: int,
    process_id: int,
    run_id: str,
) -> dict[str, str]:
    env = dict(env_base)
    env.update(
        PATHWAY_THREADS=str(threads),
        PATHWAY_PROCESSES=str(processes),
        PATHWAY_FIRST_PORT=str(first_port),
        PATHWAY_PROCESS_ID=str(process_id),
        PATHWAY_RUN_ID=run_id,
    )
    env.update(_chip_env(env_base, processes, process_id))
    return env


def spawn_program(
    *,
    threads: int,
    processes: int,
    first_port: int,
    program: str,
    arguments: tuple[str, ...],
    env_base: dict[str, str],
    supervise: bool = False,
    max_restarts: int = 3,
    checkpoint_root: str | None = None,
    shrink_on_loss: bool | None = None,
    autoscale: bool | None = None,
    standbys: int | None = None,
) -> NoReturn:
    """Launch ``processes`` copies of ``program`` forming one SPMD cluster.

    With ``supervise=True`` a crashed worker does not end the run: the
    supervisor (``engine/supervisor.py``) rolls the whole group back to
    the last committed persistence checkpoint and respawns it, up to
    ``max_restarts`` times — same run id, ports and comm secret, so the
    recovered cluster resumes exactly where the snapshots left off.

    With ``standbys=K`` (or ``PATHWAY_STANDBY_COUNT``) the supervisor
    also keeps K warm-standby processes tailing the checkpoint root
    (``engine/standby.py``); a worker death is then absorbed by
    promoting one — the survivors rejoin in place and never restart —
    with the whole-group restart above as the fallback tier.

    Elastic rescale: relaunching a supervised run with a DIFFERENT ``-n``
    on the same ``--checkpoint-root`` is supported — the supervisor
    records the new topology in the incarnation lease and the workers
    re-partition checkpointed state by shard range on resume.  With
    ``shrink_on_loss=True`` (or ``PATHWAY_DEGRADED_SHRINK=1``) the
    supervisor performs that rescale on its own when the same worker
    fails every attempt of a spent restart budget — a permanently lost
    host completes the run at the surviving count instead of failing it.
    """
    click.echo(
        f"[pathway_tpu] launching SPMD cluster: {processes} process(es), "
        f"ports {first_port}..{first_port + processes - 1}"
        + (f", supervised (max {max_restarts} restarts)" if supervise else ""),
        err=True,
    )
    run_id = str(uuid.uuid4())
    # every worker must hold the same mesh handshake secret
    # (engine/comm.py); honor a deployment-provided one, else mint one
    # for this run
    env_base = dict(env_base)
    env_base.setdefault("PATHWAY_COMM_SECRET", secrets.token_hex(16))
    # one trace per run: every worker inherits this traceparent, so its
    # epoch/commit/recovery spans correlate into a single trace in any
    # OTLP collector (worker 0 re-broadcasts it over the mesh for workers
    # launched outside spawn); restarts keep it — a recovery is part of
    # the same run's story
    from pathway_tpu.engine.telemetry import mint_traceparent

    env_base.setdefault("TRACEPARENT", mint_traceparent())
    if autoscale:
        # the workers gate their load beacons + autoscaler panel wiring on
        # the same knob the supervisor's controller reads
        env_base["PATHWAY_AUTOSCALE"] = "1"

    if supervise:
        from pathway_tpu.engine.supervisor import (
            ENV_ATTEMPT,
            ENV_INCARNATION,
            Supervisor,
            SupervisorError,
        )

        def spawn_one(
            process_id: int, attempt: int, n_workers: int = processes
        ) -> subprocess.Popen:
            # n_workers is the CURRENT cluster size (the supervisor passes
            # it explicitly so a degraded-mode shrink launches the smaller
            # topology with a matching PATHWAY_PROCESSES)
            env = _cluster_env(
                env_base,
                threads=threads,
                processes=n_workers,
                first_port=first_port,
                process_id=process_id,
                run_id=run_id,
            )
            env[ENV_ATTEMPT] = str(attempt)
            # the supervisor bumps the root's incarnation lease before
            # each attempt and exports it into ITS environ; copy it into
            # the worker env so persistence fencing and the mesh handshake
            # see the incarnation this attempt runs under
            from pathway_tpu.internals.config import env_raw

            incarnation = env_raw(ENV_INCARNATION)
            if incarnation is not None:
                env[ENV_INCARNATION] = incarnation
            # exported by the supervisor around a STANDBY spawn (same
            # env-export trick as the incarnation): the process boots
            # into the tail loop instead of the worker path
            standby_id = env_raw("PATHWAY_STANDBY_ID")
            if standby_id is not None:
                env["PATHWAY_STANDBY_ID"] = standby_id
            return subprocess.Popen([program, *arguments], env=env)

        def echo_post_mortem(post_mortem: dict) -> None:
            for wid, info in sorted(post_mortem.get("workers", {}).items()):
                click.echo(
                    f"[pathway_tpu] worker {wid}: "
                    f"{len(info.get('dumps', []))} flight-recorder dump(s) "
                    f"(last reason: {(info.get('reasons') or [None])[-1]}) — "
                    f"inspect with `pathway_tpu blackbox {checkpoint_root}`",
                    err=True,
                )

        try:
            result = Supervisor(
                spawn_one,
                processes,
                max_restarts=max_restarts,
                checkpoint_root=checkpoint_root,
                shrink_on_loss=shrink_on_loss,
                autoscale=autoscale,
                standbys=standbys,
            ).run()
        except SupervisorError as exc:
            click.echo(f"[pathway_tpu] {exc}", err=True)
            # the crash-loop black boxes are the post-mortem evidence —
            # point the operator at them before giving up
            echo_post_mortem(exc.post_mortem)
            sys.exit(1)
        if result.restarts:
            click.echo(
                f"[pathway_tpu] recovered after {result.restarts} restart(s) "
                f"(last failure: {result.last_failure})",
                err=True,
            )
        for promo in result.promotions:
            click.echo(
                f"[pathway_tpu] standby promotion: standby "
                f"{promo['standby']} adopted worker {promo['worker']} in "
                f"{promo.get('duration_s')}s on attempt "
                f"{promo.get('attempt')} ({promo.get('reason')}); the "
                "surviving workers rejoined in place without a restart",
                err=True,
            )
        for rescale in result.rescales:
            kind = rescale.get("kind")
            if kind == "autoscale":
                click.echo(
                    f"[pathway_tpu] autoscale ({rescale.get('action')}): "
                    f"cluster rescaled {rescale['from']} -> {rescale['to']} "
                    f"worker(s) via live shard handoff on attempt "
                    f"{rescale['attempt']} ({rescale.get('reason')}); "
                    f"{rescale.get('moving_shards')} shard(s) changed owner",
                    err=True,
                )
            elif kind == "autoscale-fallback":
                click.echo(
                    f"[pathway_tpu] autoscale fallback: live handoff "
                    f"{rescale['from']} -> {rescale['to']} worker(s) faulted "
                    f"on attempt {rescale['attempt']}; applied the target "
                    f"topology via restart-based rescale instead "
                    f"({rescale.get('reason')})",
                    err=True,
                )
            else:
                click.echo(
                    f"[pathway_tpu] degraded-mode shrink: worker "
                    f"{rescale['lost_worker']} treated as permanently lost on "
                    f"attempt {rescale['attempt']} — cluster rescaled "
                    f"{rescale['from']} -> {rescale['to']} worker(s); state "
                    "re-partitioned by shard range",
                    err=True,
                )
        # corruption fallback can happen WITHOUT any crash (root damaged at
        # rest before launch): report provenance whenever a worker rejected
        # generations, not only after restarts
        for wid, info in sorted(result.recovery.items()):
            rejected = [g for g, _ in info.get("rejected") or []]
            if not rejected and not result.restarts:
                continue
            click.echo(
                f"[pathway_tpu] worker {wid}: resumed from verified "
                f"generation {info.get('recovered_from')} "
                f"(now at {info.get('generation')})"
                + (f", rejected damaged generation(s) {rejected}"
                   if rejected else ""),
                err=True,
            )
        echo_post_mortem(result.post_mortem)
        sys.exit(0)

    handles: list[subprocess.Popen] = []
    try:
        # spawn inside the try: a mid-spawn failure (EAGAIN, missing
        # program) must still terminate the workers already started, or
        # they hang forever waiting for mesh peers
        for process_id in range(processes):
            handles.append(
                subprocess.Popen(
                    [program, *arguments],
                    env=_cluster_env(
                        env_base,
                        threads=threads,
                        processes=processes,
                        first_port=first_port,
                        process_id=process_id,
                        run_id=run_id,
                    ),
                )
            )
        for handle in handles:
            handle.wait()
    finally:
        for handle in handles:
            handle.terminate()
    codes = [handle.returncode for handle in handles]
    # a signal-killed worker (negative returncode) must not read as success;
    # report it with the conventional 128+signum shell encoding
    sys.exit(max(c if c >= 0 else 128 - c for c in codes))


def _recording_env(
    *,
    access: str | None = None,
    record_path: str | None = None,
    mode: str | None = None,
    continue_after_replay: bool = False,
) -> dict[str, str]:
    """Base environment for record/replay runs (PATHWAY_* protocol)."""
    env = os.environ.copy()
    if record_path is not None:
        env["PATHWAY_REPLAY_STORAGE"] = record_path
    if access is not None:
        env["PATHWAY_SNAPSHOT_ACCESS"] = access
    if mode is not None:
        env["PATHWAY_PERSISTENCE_MODE"] = mode
        env["PATHWAY_REPLAY_MODE"] = mode
    if continue_after_replay:
        env["PATHWAY_CONTINUE_AFTER_REPLAY"] = "true"
    return env


@click.group
@click.version_option(version=pw.__version__, prog_name="pathway_tpu")
def cli() -> None:
    pass


_SPAWN_SETTINGS = {"allow_interspersed_args": False, "show_default": True}


@cli.command(context_settings=_SPAWN_SETTINGS)
@click.option("-t", "--threads", metavar="N", type=click.IntRange(min=1), default=1, help="worker threads per spawned process")
@click.option("-n", "--processes", metavar="N", type=click.IntRange(min=1), default=1, help="cluster size (identical SPMD processes)")
@click.option("--first-port", metavar="PORT", type=int, default=10000, help="base port of the worker TCP mesh")
@click.option("--record", is_flag=True, help="capture every connector's input stream while running")
@click.option("--record-path", type=str, default="record", help="where the captured stream is written")
@click.option(
    "--jax-distributed",
    is_flag=True,
    help="form a multi-host DEVICE mesh too: each process calls "
    "jax.distributed.initialize so jax.devices() spans the cluster "
    "(coordinator derived from the PATHWAY_* env)",
)
@click.option(
    "--supervise",
    is_flag=True,
    help="restart the cluster from the last committed persistence "
    "checkpoint when a worker dies (engine/supervisor.py)",
)
@click.option(
    "--max-restarts",
    metavar="N",
    type=click.IntRange(min=0),
    default=3,
    help="supervised mode: give up after N recoveries",
)
@click.option(
    "--checkpoint-root",
    metavar="PATH",
    type=str,
    default=None,
    help="supervised mode: the program's filesystem persistence root, so "
    "recovery provenance (which verified generation each worker resumed "
    "from) is reported after the run",
)
@click.option(
    "--shrink-on-loss",
    is_flag=True,
    default=None,
    help="supervised mode: when the SAME worker fails every attempt of a "
    "spent restart budget (a permanently lost host, not a crash loop), "
    "rescale the cluster to the surviving count instead of failing — "
    "checkpointed state re-partitions by shard range on resume "
    "(PATHWAY_DEGRADED_SHRINK=1 is the env form)",
)
@click.option(
    "--autoscale",
    is_flag=True,
    default=None,
    help="supervised mode: arm the load-adaptive scale controller — "
    "sustained output staleness grows the cluster, sustained idleness "
    "shrinks it, applied by live shard handoff with restart fallback "
    "(bounds/thresholds via PATHWAY_AUTOSCALE_* knobs; "
    "PATHWAY_AUTOSCALE=1 is the env form; requires --checkpoint-root)",
)
@click.option(
    "--standbys",
    metavar="K",
    type=click.IntRange(min=0),
    default=None,
    help="supervised mode: keep K warm-standby processes tailing the "
    "checkpoint root (engine/standby.py) so a worker death is absorbed "
    "by promoting one — survivors rejoin in place, no group restart — "
    "with restart as the fallback tier (PATHWAY_STANDBY_COUNT is the "
    "env form; requires --checkpoint-root)",
)
@click.argument("program")
@click.argument("arguments", nargs=-1)
def spawn(threads, processes, first_port, record, record_path, jax_distributed, supervise, max_restarts, checkpoint_root, shrink_on_loss, autoscale, standbys, program, arguments):
    """Run PROGRAM as an SPMD cluster of identical processes.

    Re-running a supervised program with a different ``-n`` against the
    same ``--checkpoint-root`` performs an elastic rescale: resume
    re-partitions the committed snapshots by shard range under the new
    worker count (see docs/fault_tolerance.md, "Elastic rescale").
    """
    env = (
        _recording_env(
            access="record", record_path=record_path, continue_after_replay=True
        )
        if record
        else os.environ.copy()
    )
    if jax_distributed:
        env["PATHWAY_JAX_DISTRIBUTED"] = "1"
    spawn_program(
        threads=threads,
        processes=processes,
        first_port=first_port,
        program=program,
        arguments=arguments,
        env_base=env,
        supervise=supervise,
        max_restarts=max_restarts,
        checkpoint_root=checkpoint_root,
        shrink_on_loss=shrink_on_loss,
        autoscale=autoscale,
        standbys=standbys,
    )


@cli.command(context_settings=_SPAWN_SETTINGS)
@click.option("-t", "--threads", metavar="N", type=click.IntRange(min=1), default=1, help="worker threads per spawned process")
@click.option("-n", "--processes", metavar="N", type=click.IntRange(min=1), default=1, help="cluster size (identical SPMD processes)")
@click.option("--first-port", metavar="PORT", type=int, default=10000, help="base port of the worker TCP mesh")
@click.option("--record-path", type=str, default="record", help="where the captured stream was written")
@click.option(
    "--mode",
    type=click.Choice(["batch", "speedrun"], case_sensitive=False),
    help="replay pacing: one batch, or recorded timing",
)
@click.option(
    "--continue",
    "continue_after_replay",
    is_flag=True,
    help="after the recording drains, keep consuming live connector data",
)
@click.argument("program")
@click.argument("arguments", nargs=-1)
def replay(threads, processes, first_port, record_path, mode, continue_after_replay, program, arguments):
    """Re-run PROGRAM against a previously captured input stream."""
    spawn_program(
        threads=threads,
        processes=processes,
        first_port=first_port,
        program=program,
        arguments=arguments,
        env_base=_recording_env(
            access="replay",
            record_path=record_path,
            mode=mode,
            continue_after_replay=continue_after_replay,
        ),
    )


@cli.command()
@click.option(
    "--worker",
    metavar="N",
    type=int,
    default=None,
    help="audit only this worker's checkpoint shard",
)
@click.option(
    "--json", "as_json", is_flag=True, help="emit the machine-readable report"
)
@click.option(
    "--repair",
    is_flag=True,
    help="quarantine damaged generations above each worker's newest "
    "verified one (moved to quarantine/<worker>/, kept for forensics), "
    "then re-audit — the deliberate unblock for configurations that "
    "refuse to fall back silently",
)
@click.argument("root", type=click.Path(exists=True, file_okay=False))
def scrub(worker, as_json, repair, root):
    """Audit a filesystem persistence ROOT offline.

    Verifies every retained checkpoint generation chunk-by-chunk
    (integrity frames + manifest digests) without mutating anything
    (unless --repair), and reports per-generation health.  Exits non-zero
    when any worker's NEWEST generation fails verification — recovery
    would silently fall back to an older generation, which deserves
    operator attention.
    """
    import json as _json

    from pathway_tpu.engine.persistence import (
        FileBackend,
        repair_root,
        scrub_root,
    )

    backend = FileBackend(root)
    if repair:
        for action in repair_root(backend, worker=worker):
            click.echo(f"[repair] {action}", err=True)
    report = scrub_root(backend, worker=worker)
    if as_json:
        click.echo(_json.dumps(report, indent=2, sort_keys=True))
    else:
        click.echo(f"scrub of {report['backend']}")
        if report.get("error"):
            click.echo(f"  ERROR: {report['error']}")
        lease = report.get("lease")
        if lease is not None:
            if lease.get("ok"):
                beacons = lease.get("progress_workers") or []
                click.echo(
                    f"  lease: incarnation {lease['incarnation']} "
                    f"(owner: {lease.get('owner')})"
                    + (f", topology {lease['workers']} worker(s)"
                       if isinstance(lease.get("workers"), int) else "")
                    + (f", progress beacons for workers {beacons}"
                       if beacons else "")
                )
            else:
                click.echo(f"  lease: DAMAGED — {lease.get('error')}")
            for sid, beacon in sorted((lease.get("standbys") or {}).items()):
                cursors = beacon.get("cursors") or {}
                trail = ", ".join(
                    f"w{w}@g{g}"
                    for w, g in sorted(
                        cursors.items(), key=lambda item: int(item[0])
                    )
                )
                click.echo(
                    f"  standby {sid}: apply lag {beacon.get('lag_s')}s, "
                    f"{beacon.get('verified_chunks')} chunk(s) verified"
                    + (f", cursors {trail}" if trail
                       else ", no generations applied yet")
                )
            promos = lease.get("promotions") or []
            if promos:
                click.echo(f"  promotion history ({len(promos)}):")
                for p in promos:
                    click.echo(
                        f"    standby {p.get('standby')} -> worker "
                        f"{p.get('worker')} in {p.get('duration_s')}s on "
                        f"attempt {p.get('attempt')} ({p.get('reason')})"
                    )
            promote = lease.get("promote")
            if promote and promote.get("pending_request"):
                click.echo(
                    "  promotion IN FLIGHT (acks: "
                    f"{', '.join(promote.get('acks') or []) or 'none'})"
                )
        topo = report.get("topology")
        if topo is not None:
            history = topo.get("history") or []
            if len(history) > 1:
                trail = " -> ".join(
                    f"{h.get('workers')}@inc{h.get('incarnation')}"
                    for h in history
                )
                click.echo(f"  rescale history: {trail}")
        bb = report.get("blackbox")
        if bb is not None:
            click.echo(
                f"  blackbox: {bb['dumps']} flight-recorder dump(s) "
                f"for worker(s) {bb['workers']}"
                + (f", {len(bb['unreadable'])} unreadable"
                   if bb["unreadable"] else "")
            )
        if not report["workers"] and not report.get("error"):
            click.echo("  no checkpoint state found")
        for wid, wrep in sorted(report["workers"].items()):
            status = "OK" if wrep["ok"] else "DAMAGED"
            if wrep.get("orphaned"):
                status = f"ORPHANED ({wrep.get('status', 'fenced, pending GC')})"
            elif wrep.get("pending_repartition"):
                status += " (old topology, pending repartition)"
            click.echo(
                f"  worker {wid}: {status} — newest generation "
                f"{wrep['newest']}, newest verified {wrep['newest_verified']}"
                + (" (legacy pre-manifest metadata)"
                   if wrep["legacy_metadata"] else "")
            )
            pointer_error = (wrep.get("pointer") or {}).get("error")
            if pointer_error:
                click.echo(f"    metadata pointer: {pointer_error}")
            for entry in wrep["generations"]:
                mark = "ok" if entry["ok"] else "CORRUPT"
                stamp = entry.get("incarnation")
                topo_stamp = entry.get("topology")
                notes = []
                if stamp:
                    notes.append(f"incarnation {stamp}")
                if topo_stamp:
                    notes.append(f"topology {topo_stamp}")
                if entry.get("repartitioned_from"):
                    notes.append(
                        f"repartitioned from {entry['repartitioned_from']}"
                    )
                click.echo(
                    f"    generation {entry['generation']}: {mark}"
                    + (f" ({', '.join(notes)})" if notes else "")
                )
                for problem in entry["problems"]:
                    click.echo(f"      - {problem}")
    click.echo(
        f"[pathway_tpu] scrub: {'clean' if report['ok'] else 'DAMAGE FOUND'}",
        err=True,
    )
    sys.exit(0 if report["ok"] else 1)


@cli.command()
@click.option(
    "--worker",
    metavar="N",
    type=int,
    default=None,
    help="show only this worker's dumps",
)
@click.option(
    "--tail",
    metavar="N",
    type=click.IntRange(min=1),
    default=20,
    help="events to show from the end of each dump's ring",
)
@click.option(
    "--json", "as_json", is_flag=True, help="emit the raw dumps as JSON"
)
@click.argument("root", type=click.Path(exists=True, file_okay=False))
def blackbox(worker, tail, as_json, root):
    """Pretty-print crash flight-recorder dumps under a persistence ROOT.

    Workers dump their bounded event ring (epoch transitions, commit
    publishes, comm reconnects, injected faults) to ``<ROOT>/blackbox/``
    when they crash or a fault fires; the supervisor summarizes them on
    ``SupervisorResult.post_mortem``.  This command renders the full
    dumps for post-mortem analysis.  Exits non-zero when no dump exists.
    """
    import datetime
    import json as _json

    from pathway_tpu.engine.flight_recorder import gather_dumps

    dumps = gather_dumps(root)
    if worker is not None:
        dumps = {w: d for w, d in dumps.items() if w == worker}
    if not dumps:
        # missing or empty blackbox/: a clear non-zero exit, whatever the
        # output mode — an operator piping --json must still see why
        click.echo(
            f"[pathway_tpu] no flight-recorder dumps under {root}/blackbox "
            "— nothing crashed there, or this is not a persistence root",
            err=True,
        )
        if as_json:
            click.echo(_json.dumps({}))
        sys.exit(1)
    if as_json:
        click.echo(_json.dumps(dumps, indent=2, sort_keys=True))
        sys.exit(0)

    def when(ts):
        # best-effort like the gather layer: a parseable-but-partial dump
        # (hand-edited, older format) must render, not traceback
        if not isinstance(ts, (int, float)):
            return "--:--:--.---"
        return datetime.datetime.fromtimestamp(ts).strftime("%H:%M:%S.%f")[:-3]

    for wid, payloads in sorted(dumps.items()):
        for payload in payloads:
            events = payload.get("events") or []
            click.echo(
                f"worker {wid} · attempt {payload.get('attempt')} · "
                f"pid {payload.get('pid')} · run {payload.get('run_id')}"
            )
            click.echo(f"  reason: {payload.get('reason')}")
            if payload.get("trace_parent"):
                click.echo(f"  trace:  {payload['trace_parent']}")
            click.echo(
                f"  events: {len(events)} recorded, last {min(tail, len(events))}:"
            )
            for ev in events[-tail:]:
                detail = ", ".join(
                    f"{k}={v}"
                    for k, v in ev.items()
                    if k not in ("ts", "mono", "seq", "kind")
                )
                click.echo(
                    f"    {when(ev.get('ts'))}  #{str(ev.get('seq', '?')):>5}  "
                    f"{str(ev.get('kind', '?')):<22}{detail}"
                )
            profile = payload.get("profiler")
            if profile:
                # where the time went, not just what happened: the final
                # profiler snapshot captured at dump time
                from pathway_tpu.engine.profiler import render_snapshot

                for line in render_snapshot(profile).splitlines():
                    click.echo(f"  {line}")
            freshness = payload.get("freshness")
            if freshness:
                # ...and what was STUCK: the final watermark/backlog
                # snapshot (engine/freshness.py)
                from pathway_tpu.engine.freshness import render_freshness

                for line in render_freshness(freshness).splitlines():
                    click.echo(f"  {line}")
            device = payload.get("device")
            if device:
                # ...and what the DEVICE was doing: the final executor
                # snapshot (pathway_tpu/device/telemetry.py)
                from pathway_tpu.device import render_device_snapshot

                for line in render_device_snapshot(device).splitlines():
                    click.echo(f"  {line}")
            else:
                # pre-device-observability dumps carry no device key —
                # an explicit empty state, never a KeyError
                click.echo("  device: (no snapshot in this dump)")
            autoscaler = payload.get("autoscaler")
            if autoscaler:
                # ...and what the scale controller was deciding: the
                # supervisor-maintained state (engine/autoscaler.py) at
                # dump time, with the tail of the decision log
                click.echo(
                    "  autoscaler: target "
                    f"{autoscaler.get('target_workers')} worker(s) · "
                    f"budget left {autoscaler.get('budget_left')} · "
                    f"handoff state "
                    f"{autoscaler.get('handoff_state') or 'idle'}"
                )
                for entry in (autoscaler.get("decisions") or [])[-5:]:
                    click.echo(
                        f"    {entry.get('action', '?'):<18}"
                        + ", ".join(
                            f"{k}={v}"
                            for k, v in entry.items()
                            if k not in ("action", "at")
                        )
                    )
            serving = payload.get("serving")
            if serving:
                # ...and what the SERVING edge was refusing: admission
                # occupancy + shed/drain state (engine/serving.py) at
                # dump time, with the quarantine tail
                limits = serving.get("limits") or {}
                flags = [
                    flag
                    for flag, on in (
                        ("degraded", serving.get("degraded")),
                        ("draining", serving.get("draining")),
                        ("admission off", not serving.get("enabled", True)),
                    )
                    if on
                ]
                click.echo(
                    f"  serving: {serving.get('inflight')}"
                    f"/{limits.get('inflight')} in flight · queue "
                    f"{serving.get('queue_depth')}/{limits.get('queue')}"
                    + (" · " + ", ".join(flags) if flags else "")
                )
                if serving.get("quarantined_total"):
                    click.echo(
                        "    quarantined "
                        f"{serving['quarantined_total']} request(s), last:"
                    )
                    for entry in serving.get("quarantine") or []:
                        click.echo(
                            f"      key={entry.get('key')} "
                            f"{entry.get('error')}"
                        )
    sys.exit(0)


@cli.command()
@click.option(
    "--json", "as_json", is_flag=True, help="emit the report as JSON"
)
@click.option(
    "--rules",
    "rule_ids",
    metavar="ID[,ID...]",
    default=None,
    help="run only these rule ids (default: every rule)",
)
@click.option(
    "--list-rules", is_flag=True, help="print the rule catalogue and exit"
)
@click.option(
    "--update-config-docs",
    is_flag=True,
    help="regenerate docs/configuration.md from the env-knob registry "
    "(internals/config.py:ENV_KNOBS) and exit",
)
@click.argument("paths", nargs=-1, type=click.Path(exists=True))
def lint(as_json, rule_ids, list_rules, update_config_docs, paths):
    """Run the repo-native static analyzer over PATHS.

    Default paths are the installed ``pathway_tpu`` package and its
    sibling ``tests/`` tree.  Rules prove thread-context safety (no
    blocking calls on the epoch loop or signal paths, timed waits on
    supervised background threads), lock-order consistency, env-knob and
    metric-name registry discipline, jit recompile discipline, and the
    chaos-suite sleep policy — see ``docs/static_analysis.md``.

    Exits non-zero when any unsuppressed finding remains.  Suppressions
    (``# pathway-lint: disable=<rule> — <reason>``) are audited: a
    reasonless or useless suppression is itself a finding.
    """
    from pathway_tpu.analysis import RULES, report_to_text, run_lint

    if list_rules:
        width = max(len(rid) for rid in RULES)
        for rid in sorted(RULES):
            click.echo(f"{rid:<{width}}  {RULES[rid].doc}")
        sys.exit(0)
    pkg_dir = os.path.dirname(os.path.abspath(pw.__file__))
    repo_root = os.path.dirname(pkg_dir)
    if update_config_docs:
        from pathway_tpu.internals.config import render_env_docs

        doc_path = os.path.join(repo_root, "docs", "configuration.md")
        os.makedirs(os.path.dirname(doc_path), exist_ok=True)
        with open(doc_path, "w", encoding="utf-8") as f:
            f.write(render_env_docs())
        click.echo(f"[pathway_tpu] wrote {doc_path}")
        sys.exit(0)
    if not paths:
        paths = [pkg_dir]
        tests_dir = os.path.join(repo_root, "tests")
        if os.path.isdir(tests_dir):
            paths.append(tests_dir)
    selected = None
    if rule_ids:
        selected = [r.strip() for r in rule_ids.split(",") if r.strip()]
    try:
        report = run_lint(paths, rules=selected)
    except ValueError as exc:  # unknown rule id
        click.echo(f"[pathway_tpu] {exc}", err=True)
        sys.exit(2)
    click.echo(report_to_text(report, as_json=as_json))
    sys.exit(0 if report.ok else 1)


@cli.command()
@click.option(
    "--top",
    metavar="N",
    type=click.IntRange(min=1),
    default=None,
    help="operators to show (default: the PATHWAY_PROFILE_TOP knob)",
)
@click.option(
    "--json", "as_json", is_flag=True, help="emit the raw snapshot(s) as JSON"
)
@click.argument("source", type=click.Path(exists=True))
def profile(top, as_json, source):
    """Render a per-operator attribution tree from profiler output.

    SOURCE is either a profiler snapshot JSON (written at run end when
    ``PATHWAY_PROFILE=1`` and ``PATHWAY_PROFILE_OUTPUT=<path>`` are set)
    or a filesystem persistence root, whose flight-recorder dumps under
    ``blackbox/`` carry final profiler snapshots (see
    ``docs/observability.md``).  Exits non-zero when SOURCE holds no
    profile.
    """
    import json as _json

    from pathway_tpu.engine.profiler import render_snapshot
    from pathway_tpu.internals.config import env_int

    top = top or env_int("PATHWAY_PROFILE_TOP")
    # (label, profiler snapshot, device snapshot or None) — positionally
    # paired, because one worker/attempt can leave several dumps
    # (watchdog + crash) whose labels collide; ABSENT marks a bare
    # PATHWAY_PROFILE_OUTPUT snapshot with no dump context at all, and
    # None a dump that predates device observability (explicit empty
    # state)
    ABSENT = object()
    snapshots: list[tuple[str, dict, Any]] = []
    if os.path.isdir(source):
        from pathway_tpu.engine.flight_recorder import gather_dumps

        for wid, payloads in sorted(gather_dumps(source).items()):
            for payload in payloads:
                label = f"worker {wid} · attempt {payload.get('attempt')}"
                prof = payload.get("profiler")
                if prof:
                    snapshots.append((label, prof, payload.get("device")))
    else:
        try:
            with open(source, encoding="utf-8") as f:
                payload = _json.load(f)
        except (OSError, ValueError) as exc:
            click.echo(f"[pathway_tpu] unreadable snapshot: {exc}", err=True)
            sys.exit(2)
        # tolerate any JSON top level (the command's own --json output is
        # a list) — anything without a snapshot dict falls through to the
        # friendly no-profile exit below
        prof = (
            payload.get("profiler", payload)
            if isinstance(payload, dict)
            else None
        )
        if isinstance(prof, dict) and "operators" in prof:
            # a flight-recorder dump file gets the same device section
            # (or empty state) as the directory form; a bare
            # PATHWAY_PROFILE_OUTPUT snapshot has no dump context and
            # gets neither
            device = (
                payload.get("device")
                if isinstance(payload, dict) and "profiler" in payload
                else ABSENT
            )
            snapshots.append((source, prof, device))
    if not snapshots:
        click.echo(
            f"[pathway_tpu] no profiler snapshot in {source} — run with "
            "PATHWAY_PROFILE=1 (and PATHWAY_PROFILE_OUTPUT=<path>, or read "
            "a persistence root with flight-recorder dumps)",
            err=True,
        )
        sys.exit(1)
    if as_json:
        # a list, not a dict: one worker/attempt can leave several dumps
        # (watchdog + crash) whose labels collide — none may be dropped
        entries = []
        for label, snap, device in snapshots:
            entry: dict = {"label": label, "snapshot": snap}
            if device is not ABSENT:
                # the machine-readable form carries the same device
                # section the text render shows (null = a dump that
                # predates device observability)
                entry["device"] = device
            entries.append(entry)
        click.echo(_json.dumps(entries, indent=2, sort_keys=True))
        sys.exit(0)
    for label, snap, device in snapshots:
        if len(snapshots) > 1:
            click.echo(label)
        click.echo(render_snapshot(snap, top=top))
        if device is ABSENT:
            continue
        if device:
            from pathway_tpu.device import render_device_snapshot

            click.echo(render_device_snapshot(device))
        else:
            click.echo("device: (no snapshot in this dump)")
    sys.exit(0)


@cli.command()
@click.option(
    "--url",
    metavar="URL",
    type=str,
    default=None,
    help="full /status URL (overrides --port/--process-id)",
)
@click.option(
    "--port",
    metavar="PORT",
    type=int,
    default=None,
    help="monitoring HTTP port (default: PATHWAY_MONITORING_HTTP_PORT, "
    "else 20000 + process id)",
)
@click.option(
    "--process-id",
    metavar="N",
    type=int,
    default=0,
    help="worker whose endpoint to poll (port defaults to 20000 + N)",
)
@click.option(
    "--interval",
    metavar="SECONDS",
    type=float,
    default=None,
    help="refresh interval (default: the PATHWAY_STATUS_REFRESH_S knob)",
)
@click.option(
    "--once", is_flag=True, help="render a single frame and exit (no loop)"
)
@click.option(
    "--json", "as_json", is_flag=True, help="emit the raw /status JSON"
)
def top(url, port, process_id, interval, once, as_json):
    """Live per-operator backlog + freshness view of a running pipeline.

    Polls ``GET /status`` on the monitoring HTTP server (enable it with
    ``pw.run(with_http_server=True)`` or ``PATHWAY_MONITORING_HTTP_PORT``)
    and renders epoch rate, per-output staleness and end-to-end latency
    quantiles (``freshness.*``), the ranked ``backlog.*`` wait points,
    and the per-operator progress table — see ``docs/observability.md``,
    "Freshness & backpressure".  Exits non-zero with a clear message when
    the endpoint is unreachable.
    """
    import json as _json
    import time as _time_mod

    from pathway_tpu.internals.config import env_float
    from pathway_tpu.internals.top import (
        StatusUnavailable,
        fetch_status,
        render_top,
    )

    url = _monitoring_url(url, port, process_id, "status")
    if interval is None:
        interval = env_float("PATHWAY_STATUS_REFRESH_S")  # declared default 1.0
    # an explicit small value clamps (never silently reverts to the
    # default); 0.1 s is the floor so a typo cannot hot-spin the server
    interval = max(0.1, float(interval))
    prev = None
    prev_t = None
    while True:
        try:
            status = fetch_status(url)
        except StatusUnavailable as exc:
            click.echo(f"[pathway_tpu] {exc}", err=True)
            sys.exit(1)
        now = _time_mod.monotonic()
        if as_json:
            click.echo(_json.dumps(status, indent=2, sort_keys=True))
        else:
            if not once:
                click.clear()
            # epoch rate derives from the MEASURED elapsed time between
            # polls, not the configured interval — slow fetches must not
            # overstate the rate
            click.echo(
                render_top(
                    status,
                    prev,
                    interval_s=(now - prev_t) if prev_t else None,
                )
            )
        if once:
            sys.exit(0)
        prev, prev_t = status, now
        _time_mod.sleep(interval)


@cli.command()
@click.argument(
    "dump", required=False, type=click.Path(exists=True, dir_okay=False)
)
@click.option(
    "--url",
    metavar="URL",
    type=str,
    default=None,
    help="full /status URL (overrides --port/--process-id)",
)
@click.option(
    "--port",
    metavar="PORT",
    type=int,
    default=None,
    help="monitoring HTTP port (default: PATHWAY_MONITORING_HTTP_PORT, "
    "else 20000 + process id)",
)
@click.option(
    "--process-id",
    metavar="N",
    type=int,
    default=0,
    help="worker whose endpoint to poll (port defaults to 20000 + N)",
)
@click.option(
    "-n",
    "--limit",
    metavar="N",
    type=int,
    default=10,
    help="waterfalls to render (default 10)",
)
@click.option(
    "--recent",
    is_flag=True,
    help="newest-first instead of slowest-first",
)
@click.option(
    "--json", "as_json", is_flag=True, help="emit the raw trace JSON"
)
def requests(dump, url, port, process_id, limit, recent, as_json):
    """Slowest-request waterfalls from the live span buffer or a dump.

    Reads the finished-request trace ring (``engine/tracing.py``) either
    from a running pipeline's ``GET /status`` ``requests`` section or —
    with a DUMP argument — from a flight-recorder dump file's
    ``requests`` payload, and renders each trace as a span waterfall:
    admission, coalesce, device dispatch, and generation stages with
    their offsets and durations.  See ``docs/observability.md``,
    "Request tracing & SLOs".
    """
    import json as _json

    from pathway_tpu.internals.top import (
        StatusUnavailable,
        fetch_status,
        render_requests,
    )

    if dump is not None:
        try:
            with open(dump) as f:
                payload = _json.load(f)
        except (OSError, ValueError) as exc:
            click.echo(f"[pathway_tpu] cannot read dump {dump}: {exc}", err=True)
            sys.exit(1)
        section = payload.get("requests") or {}
    else:
        status_url = _monitoring_url(url, port, process_id, "status")
        try:
            status = fetch_status(status_url)
        except StatusUnavailable as exc:
            click.echo(f"[pathway_tpu] {exc}", err=True)
            sys.exit(1)
        section = status.get("requests") or {}
    traces = section.get("recent" if recent else "slowest") or []
    if as_json:
        click.echo(_json.dumps(traces[:limit], indent=2, sort_keys=True))
        sys.exit(0)
    click.echo(render_requests(traces, limit=limit))
    sys.exit(0)


def _monitoring_url(url: str | None, port: int | None, process_id: int,
                    endpoint: str) -> str:
    """Resolve a monitoring-server URL the way ``top`` does: explicit
    ``--url`` wins, else ``--port``/``PATHWAY_MONITORING_HTTP_PORT``/the
    20000 + process-id default, with ``endpoint`` as the path."""
    if url is not None:
        return url
    from pathway_tpu.engine.http_server import monitoring_port
    from pathway_tpu.internals.config import env_int

    if port is None:
        port = env_int("PATHWAY_MONITORING_HTTP_PORT")
    return f"http://127.0.0.1:{monitoring_port(process_id, port)}/{endpoint}"


@cli.command()
@click.option(
    "--url",
    metavar="URL",
    type=str,
    default=None,
    help="full /trace URL (overrides --port/--process-id)",
)
@click.option(
    "--port",
    metavar="PORT",
    type=int,
    default=None,
    help="monitoring HTTP port (default: PATHWAY_MONITORING_HTTP_PORT, "
    "else 20000 + process id)",
)
@click.option(
    "--process-id",
    metavar="N",
    type=int,
    default=0,
    help="worker whose device to trace (port defaults to 20000 + N)",
)
@click.option(
    "--seconds",
    metavar="S",
    type=float,
    default=3.0,
    show_default=True,
    help="capture duration",
)
def trace(url, port, process_id, seconds):
    """Capture an on-demand jax.profiler trace from a running worker.

    Asks the worker's monitoring HTTP server (``GET /trace?seconds=N``)
    to run ``jax.profiler`` start/stop IN the worker process and dump a
    TensorBoard-viewable trace directory under the worker's
    ``PATHWAY_DEVICE_TRACE_DIR`` — see docs/observability.md, "Device
    observability".  Exits non-zero with the server's reason when
    capture is unavailable (no trace dir configured, capture already
    running, endpoint unreachable).
    """
    import json as _json
    import urllib.error
    import urllib.request

    target = _monitoring_url(url, port, process_id, "trace")
    sep = "&" if "?" in target else "?"
    target = f"{target}{sep}seconds={float(seconds)}"
    click.echo(
        f"[pathway_tpu] capturing {seconds:g} s of device trace via "
        f"{target} ...",
        err=True,
    )
    try:
        # the server blocks for the capture duration; pad the timeout
        with urllib.request.urlopen(target, timeout=seconds + 30.0) as r:
            payload = _json.loads(r.read().decode())
    except urllib.error.HTTPError as exc:
        try:
            reason = _json.loads(exc.read().decode()).get("error", str(exc))
        except Exception:  # noqa: BLE001 - error body is best-effort
            reason = str(exc)
        click.echo(f"[pathway_tpu] trace capture failed: {reason}", err=True)
        sys.exit(1)
    except (OSError, ValueError) as exc:
        click.echo(
            f"[pathway_tpu] cannot reach {target} ({exc}) — is the pipeline "
            "running with with_http_server=True (or "
            "PATHWAY_MONITORING_HTTP_PORT set)?",
            err=True,
        )
        sys.exit(1)
    trace_dir = payload.get("trace_dir")
    click.echo(f"[pathway_tpu] trace written to {trace_dir}")
    click.echo(f"[pathway_tpu] view with: tensorboard --logdir {trace_dir}", err=True)
    sys.exit(0)


@cli.command()
@click.option(
    "--url",
    metavar="URL",
    type=str,
    default=None,
    help="full /status URL (overrides --port/--process-id)",
)
@click.option(
    "--port",
    metavar="PORT",
    type=int,
    default=None,
    help="monitoring HTTP port (default: PATHWAY_MONITORING_HTTP_PORT, "
    "else 20000 + process id)",
)
@click.option(
    "--process-id",
    metavar="N",
    type=int,
    default=0,
    help="worker whose batch distribution to read",
)
@click.option(
    "--max-buckets",
    metavar="K",
    type=click.IntRange(min=1),
    default=8,
    show_default=True,
    help="bucket-set size budget (each bucket is one compile per callable)",
)
@click.option(
    "--json", "as_json", is_flag=True, help="emit the report as JSON"
)
@click.argument(
    "root", type=click.Path(exists=True, file_okay=False), required=False
)
def buckets(url, port, process_id, max_buckets, as_json, root):
    """Replay the observed batch-size distribution; suggest better buckets.

    Reads the ragged batch sizes the DeviceExecutor actually saw — live
    from a running worker's ``GET /status`` device section, or post-hoc
    from the flight-recorder dumps under a persistence ROOT — replays
    them against the default power-of-two policy, and reports the bucket
    set of at most ``--max-buckets`` sizes that minimizes padding waste
    (``device/bucketing.py:suggest_buckets``).  Exits non-zero when no
    batch distribution is available.
    """
    import json as _json

    from pathway_tpu.device.bucketing import (
        BucketPolicy,
        next_pow2,
        replay_waste,
        suggest_buckets,
    )

    size_counts: dict[int, int] = {}
    truncated = False
    observed_max_batch: int | None = None
    if root is not None:
        from pathway_tpu.engine.flight_recorder import gather_dumps

        for _wid, payloads in sorted(gather_dumps(root).items()):
            # the accountant ledger is cumulative PER PROCESS: a worker
            # attempt that dumped twice (watchdog then crash) repeats its
            # earlier batches in the later dump — count only the newest
            # dump of each attempt, summing across attempts (each attempt
            # is a fresh process)
            newest_per_attempt: dict[Any, dict] = {}
            for payload in payloads:
                key = payload.get("attempt")
                prev = newest_per_attempt.get(key)
                if prev is None or (payload.get("dumped_at") or 0) >= (
                    prev.get("dumped_at") or 0
                ):
                    newest_per_attempt[key] = payload
            for payload in newest_per_attempt.values():
                device_snap = payload.get("device") or {}
                try:
                    observed_max_batch = int(device_snap["default_max_batch"])
                except (KeyError, TypeError, ValueError):
                    pass
                sizes = (device_snap.get("cost") or {}).get(
                    "batch_sizes"
                ) or {}
                for size, count in sizes.items():
                    try:
                        size_counts[int(size)] = (
                            size_counts.get(int(size), 0) + int(count)
                        )
                    except (TypeError, ValueError):
                        continue
        source = f"flight-recorder dumps under {root}"
    else:
        from pathway_tpu.engine.metrics import split_labeled_name
        from pathway_tpu.internals.top import StatusUnavailable, fetch_status

        target = _monitoring_url(url, port, process_id, "status")
        try:
            status = fetch_status(target)
        except StatusUnavailable as exc:
            click.echo(f"[pathway_tpu] {exc}", err=True)
            sys.exit(1)
        device_section = status.get("device") or {}
        if device_section.get("device.batch.max"):
            observed_max_batch = int(device_section["device.batch.max"])
        for key, value in device_section.items():
            base, labels = split_labeled_name(key)
            if base != "device.batch.rows" or "rows" not in labels:
                continue
            try:
                size_counts[int(labels["rows"])] = int(value)
            except (TypeError, ValueError):
                continue
        source = target
        # the live feed exports only the most-frequent sizes
        # (device/telemetry.py:BATCH_SIZE_EXPORT_TOP); at the cap the
        # tail was dropped and the report must say so
        from pathway_tpu.device.telemetry import BATCH_SIZE_EXPORT_TOP

        truncated = len(size_counts) >= BATCH_SIZE_EXPORT_TOP
    if not size_counts:
        click.echo(
            f"[pathway_tpu] no batch-size distribution in {source} — the "
            "DeviceExecutor has not dispatched yet (or the dump predates "
            "device observability)",
            err=True,
        )
        sys.exit(1)
    largest = max(size_counts)
    # the baseline is the ANALYZED RUN's default policy: batches above
    # its max split into full-bucket chunks, so replaying against
    # next_pow2(largest) would invent waste the run never paid.  The
    # snapshot/status carries the run's PATHWAY_DEVICE_MAX_BATCH; the
    # analyst's own env is only the last-resort fallback (pre-PR-12
    # dumps)
    if observed_max_batch is None:
        from pathway_tpu.internals.config import env_int

        observed_max_batch = env_int("PATHWAY_DEVICE_MAX_BATCH")
    current = BucketPolicy(
        max_bucket=min(next_pow2(largest), int(observed_max_batch))
    ).buckets()
    current_pad, real = replay_waste(size_counts, current)
    suggested = suggest_buckets(size_counts, max_buckets=max_buckets)
    suggested_pad, _ = replay_waste(size_counts, suggested)

    def frac(pad: int) -> float:
        return pad / (pad + real) if (pad + real) else 0.0

    report = {
        "source": source,
        "batches": sum(size_counts.values()),
        "distinct_sizes": len(size_counts),
        "truncated": truncated,
        "largest": largest,
        "real_rows": real,
        "current": {
            "buckets": list(current),
            "pad_rows": current_pad,
            "waste_fraction": frac(current_pad),
        },
        "suggested": {
            "buckets": list(suggested),
            "pad_rows": suggested_pad,
            "waste_fraction": frac(suggested_pad),
        },
    }
    if as_json:
        click.echo(_json.dumps(report, indent=2, sort_keys=True))
        sys.exit(0)
    click.echo(
        f"batch distribution: {report['batches']} batch(es), "
        f"{report['distinct_sizes']} distinct size(s), largest {largest} "
        f"({source})"
    )
    if truncated:
        click.echo(
            "  note: the live /status feed exports only the most-frequent "
            "sizes — the tail of the distribution was dropped; read a "
            "flight-recorder root for the full ledger"
        )
    click.echo(
        f"  power-of-two policy {current}: {current_pad} pad row(s) "
        f"({frac(current_pad):.1%} waste)"
    )
    click.echo(
        f"  suggested buckets   {suggested}: {suggested_pad} pad row(s) "
        f"({frac(suggested_pad):.1%} waste) — "
        f"{len(suggested)} compile(s) per callable"
    )
    if suggested_pad < current_pad:
        click.echo(
            f"  apply with DeviceExecutor.register(..., policy=BucketPolicy("
            f"sizes={suggested})) for the hot callables"
        )
    else:
        click.echo("  the power-of-two policy is already near-optimal here")
    sys.exit(0)


def _load_harness():
    """Import ``benchmarks/harness.py`` by path (the benchmarks tree sits
    beside the package, not inside it)."""
    import importlib.util

    pkg_dir = os.path.dirname(os.path.abspath(pw.__file__))
    path = os.path.join(os.path.dirname(pkg_dir), "benchmarks", "harness.py")
    if not os.path.isfile(path):
        raise click.ClickException(
            f"benchmark harness not found at {path} (the `bench` command "
            "needs the repository's benchmarks/ tree)"
        )
    name = "pathway_bench_harness"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    # registered before exec: dataclass decorators resolve their module
    # through sys.modules
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@cli.command()
@click.option(
    "--smoke/--full",
    "smoke",
    default=True,
    help="suite scale: smoke (small sizes, tier-1-friendly) or full",
)
@click.option(
    "--check",
    is_flag=True,
    help="compare against committed baselines (benchmarks/baselines/); "
    "exit non-zero on a regression past the noise-tolerant thresholds",
)
@click.option(
    "--update-baselines",
    is_flag=True,
    help="write this run's medians/IQR as the new baselines",
)
@click.option(
    "--update-results",
    is_flag=True,
    help="regenerate the harness tables in benchmarks/RESULTS.md",
)
@click.option("--reps", metavar="N", type=click.IntRange(min=1), default=None,
              help="repetitions per benchmark (default: per-mode)")
@click.option("--only", metavar="NAME", multiple=True,
              help="run only these benchmarks (repeatable)")
@click.option("--baseline-dir", metavar="PATH", type=str, default=None,
              help="baseline directory override")
@click.option("--json", "json_path", metavar="PATH", type=str, default=None,
              help="also write the machine-readable results JSON here")
def bench(smoke, check, update_baselines, update_results, reps, only,
          baseline_dir, json_path):
    """Run the benchmark suite and check for regressions.

    Runs the repository's host benchmarks (``benchmarks/host_*.py`` and
    friends) in smoke or full mode, reports per-metric medians + IQR with
    an environment fingerprint, and — with ``--check`` — compares against
    the committed baselines with noise-tolerant thresholds (see
    ``docs/benchmarking.md``).
    """
    harness = _load_harness()
    mode = "smoke" if smoke else "full"
    # the check must compare against the PREVIOUSLY committed baseline,
    # loaded before the suite runs (fail fast: a missing baseline should
    # not cost minutes of benchmarking first) and before
    # --update-baselines overwrites it — otherwise `--update-baselines
    # --check` would compare the run against itself and bless any
    # regression
    try:
        prior_baseline = (
            harness.load_baseline(mode, baseline_dir=baseline_dir)
            if check
            else None
        )
        if check and prior_baseline is None and not update_baselines:
            click.echo(
                f"[pathway_tpu] no committed baseline for mode {mode!r} — "
                "run `pathway_tpu bench --update-baselines` first",
                err=True,
            )
            sys.exit(2)
        results = harness.run_suite(
            mode=mode, reps=reps, only=list(only) or None, echo=click.echo
        )
        if json_path:
            harness.write_results(results, json_path)
            click.echo(
                f"[pathway_tpu] results written to {json_path}", err=True
            )
        # the regression check runs BEFORE any baseline/RESULTS update: a
        # failing check must leave the committed files untouched, or a
        # simple re-run of the same command would report OK against the
        # freshly blessed regression
        report = (
            harness.compare(results, prior_baseline)
            if check and prior_baseline is not None
            else None
        )
        if report is not None and not report["ok"]:
            click.echo(harness.render_report(report))
            click.echo(
                "[pathway_tpu] regression detected — baseline/RESULTS "
                "updates skipped (fix or re-anchor deliberately)",
                err=True,
            )
            sys.exit(1)
        if update_baselines:
            path = harness.update_baseline(results, baseline_dir=baseline_dir)
            click.echo(f"[pathway_tpu] baseline written to {path}", err=True)
        if update_results:
            path = harness.update_results_md(results)
            click.echo(
                f"[pathway_tpu] results table updated in {path}", err=True
            )
    except harness.HarnessError as exc:
        raise click.ClickException(str(exc)) from exc
    if not check:
        sys.exit(0)
    if report is None:
        # bootstrap: no prior baseline existed; this run just created
        # the first one, so there is nothing to regress against
        click.echo(
            "[pathway_tpu] bench check: OK (bootstrap — baseline "
            "created by this run; future runs check against it)"
        )
        sys.exit(0)
    click.echo(harness.render_report(report))
    sys.exit(0)


@cli.command(name="spawn-from-env")
def spawn_from_env():
    """Re-exec ``spawn`` with arguments from PATHWAY_SPAWN_ARGS."""
    from pathway_tpu.internals.config import env_str

    spawn_args = env_str("PATHWAY_SPAWN_ARGS")
    if spawn_args is None:
        click.echo("PATHWAY_SPAWN_ARGS variable is unspecified, exiting...", err=True)
        return
    os.execl(
        sys.executable, sys.executable, "-m", "pathway_tpu", "spawn", *spawn_args.split()
    )


@cli.group()
def airbyte() -> None:
    pass


@airbyte.command(name="create-source")
@click.argument("connection")
@click.option("--image", default="airbyte/source-faker:0.1.4", help="public Airbyte source Docker image")
def create_source(connection, image):
    """Scaffold an Airbyte connection config (requires docker at runtime)."""
    from pathway_tpu.io.airbyte import write_connection_scaffold

    path = write_connection_scaffold(connection, image)
    click.echo(f"Connection `{connection}` with source `{image}` created at {path}")


def main() -> NoReturn:
    cli.main()


if __name__ == "__main__":
    main()
