"""``serve``/``submit`` CLI verbs for the crash-safe scheduler.

::

    # submit three requests (works with or without a live daemon)
    python -m multigpu_advectiondiffusion_tpu.cli submit --root runs/ \
        --job-id j1 -- diffusion3d --n 64 64 64 --iters 2000 \
        --checkpoint-every 100 --sentinel-every 100
    # start the daemon; --until-idle returns once the queue drains
    python -m multigpu_advectiondiffusion_tpu.cli serve --root runs/ \
        --max-concurrent 2 --devices 8 --until-idle
    # offline: replay + linearization-check the journal
    python -m multigpu_advectiondiffusion_tpu.cli serve --root runs/ \
        --verify --require-complete
"""

from __future__ import annotations

import argparse
import os
import sys


def configure_serve(p: argparse.ArgumentParser) -> None:
    p.add_argument("--root", required=True, metavar="DIR",
                   help="scheduler root: journal.jsonl, spool/, "
                        "jobs/<id>/ namespaces, the shared AOT cache "
                        "and the daemon's sched_events.jsonl live here")
    p.add_argument("--max-concurrent", type=int, default=1, metavar="N",
                   help="run slots: jobs admitted at once (default 1)")
    p.add_argument("--devices", type=int, default=1, metavar="P",
                   help="device budget the admission controller "
                        "carves mesh slices from (default 1)")
    p.add_argument("--mem-budget-mb", type=int, default=0, metavar="MB",
                   help="defer admission while the running jobs' "
                        "measured mem:watermark peaks plus the "
                        "candidate's expected peak exceed this "
                        "(0 = unmetered)")
    p.add_argument("--poll", type=float, default=0.2, metavar="S",
                   help="scheduler loop cadence in seconds")
    p.add_argument("--until-idle", action="store_true",
                   help="exit once every job is terminal (the gate/CI "
                        "mode); default: serve until SIGTERM/SIGINT, "
                        "which drains running jobs through their "
                        "checkpoint-and-exit-75 preemption path first")
    p.add_argument("--no-aot-cache", action="store_true",
                   help="disable the shared per-root AOT executable "
                        "cache (warm admission loses its "
                        "deserialize-instead-of-compile path)")
    p.add_argument("--verify", action="store_true",
                   help="no daemon: replay the journal, print the "
                        "queue state table, and exit nonzero when the "
                        "journal does not linearize (illegal or "
                        "out-of-order transitions)")
    p.add_argument("--require-complete", action="store_true",
                   help="with --verify: also fail when any submitted "
                        "job never reached done/failed, or the journal "
                        "has torn lines — the sched_gate.sh assertion")
    p.add_argument("--no-lease", action="store_true",
                   help="skip the single-writer lease (testing only: "
                        "two daemons on one root WILL interleave "
                        "journal appends)")
    p.set_defaults(fn=run_serve)


def configure_submit(p: argparse.ArgumentParser) -> None:
    p.add_argument("--root", required=True, metavar="DIR")
    p.add_argument("--job-id", default=None,
                   help="stable id (default: generated); also the "
                        "job's directory name under <root>/jobs/")
    p.add_argument("--priority", type=int, default=0,
                   help="higher runs first; a strictly higher arrival "
                        "preempts a running lower-priority job through "
                        "the checkpoint-and-exit-75 path")
    p.add_argument("--max-retries", type=int, default=2,
                   help="bounded retry budget per failure policy")
    p.add_argument("--devices", type=int, default=0,
                   help="device request; the scheduler grants the "
                        "largest divisor that fits the free slice "
                        "(elastic resume may re-admit on a smaller "
                        "slice than the first attempt ran on)")
    p.add_argument("--mesh-template", default="dz={devices}",
                   help="mesh spec formatted with the granted device "
                        "count when > 1 (default 'dz={devices}')")
    p.add_argument("--env", action="append", default=[],
                   metavar="KEY=VAL",
                   help="environment override for the job's worker "
                        "process; repeatable")
    p.add_argument("argv", nargs=argparse.REMAINDER,
                   help="the job's CLI request after '--': model + "
                        "flags (the scheduler owns --save/--metrics/"
                        "--resume/--mesh/--aot-cache)")
    p.set_defaults(fn=run_submit)


def run_serve(args) -> None:
    from multigpu_advectiondiffusion_tpu.service.daemon import Scheduler
    from multigpu_advectiondiffusion_tpu.service.journal import (
        Journal,
        JournalSchemaError,
        schema_stamps,
        verify_records,
    )
    from multigpu_advectiondiffusion_tpu.service.lease import (
        EXIT_LEASE_HELD,
        LeaseHeldError,
    )
    from multigpu_advectiondiffusion_tpu.service.queue import JobQueue

    if args.verify:
        journal_path = os.path.join(args.root, "journal.jsonl")
        try:
            records, torn = Journal.replay(journal_path)
        except JournalSchemaError as err:
            print(f"   PROBLEM: {err}", file=sys.stderr)
            raise SystemExit(1)
        problems = verify_records(
            records, torn=torn,
            require_complete=args.require_complete,
            schema_versions=schema_stamps(journal_path),
        )
        # the state table, rebuilt exactly the way recovery would
        with Journal(journal_path, fsync=False) as j:
            q, report = JobQueue.replay(j)
        print(f"-- journal {journal_path}: {len(records)} record(s), "
              f"{torn} torn line(s), {len(q.jobs)} job(s)")
        for rec in sorted(q.jobs.values(), key=lambda r: r.order):
            print(f"   {rec.job_id:<24} {rec.state:<13} "
                  f"attempts={rec.attempts} "
                  f"failures={len(rec.failures)} "
                  f"dt_scale={rec.dt_scale:g}")
        for msg in report.get("problems", []):
            problems.append(f"replay: {msg}")
        for msg in problems:
            print(f"   PROBLEM: {msg}", file=sys.stderr)
        if problems:
            raise SystemExit(1)
        print("-- journal linearizes")
        return None

    try:
        sched = Scheduler(
            args.root,
            max_concurrent=args.max_concurrent,
            device_budget=args.devices,
            mem_budget_bytes=args.mem_budget_mb * (1 << 20),
            poll_seconds=args.poll,
            aot_cache=not args.no_aot_cache,
            lease=not args.no_lease,
        )
    except LeaseHeldError as err:
        print(f"-- serve: {err}", file=sys.stderr)
        raise SystemExit(EXIT_LEASE_HELD)
    except ValueError as err:
        raise SystemExit(f"-- serve: {err}")
    try:
        outcome = sched.serve(until_idle=args.until_idle)
    finally:
        sched.close()
    states = outcome.get("states", {})
    print(f"-- serve: {outcome.get('reason')}; "
          + ", ".join(f"{k}={v}" for k, v in sorted(states.items())))
    if outcome.get("reason") == "stalled":
        raise SystemExit(2)
    return None


def configure_serve_requests(p: argparse.ArgumentParser) -> None:
    p.add_argument("--root", required=True, metavar="DIR",
                   help="serving root: journal.jsonl, spool/, "
                        "requests/<id>/ artifacts and the server's "
                        "serve_events.jsonl live here")
    p.add_argument("--max-batch", type=int, default=8, metavar="B",
                   help="coalescing width: compatible requests folded "
                        "onto one ensemble member axis (default 8)")
    p.add_argument("--slice-steps", type=int, default=16, metavar="N",
                   help="bounded advance slice: finished members "
                        "return and joiners enter at every N-step "
                        "boundary (default 16)")
    p.add_argument("--queue-bound", type=int, default=64, metavar="N",
                   help="backpressure: open requests beyond this shed "
                        "with a retry-after verdict (default 64)")
    p.add_argument("--retry-after", type=float, default=2.0,
                   metavar="S",
                   help="retry-after hint in shed verdicts (default 2)")
    p.add_argument("--mesh", default=None, metavar="SPEC",
                   help="serving mesh, e.g. 'members=2' or "
                        "'members=2,dz=2' — batches shard their member "
                        "axis over it (clone-padded so B tiles)")
    p.add_argument("--mem-budget-mb", type=int, default=0, metavar="MB",
                   help="cap the batch width so the estimated live "
                        "state fits (0 = unmetered)")
    p.add_argument("--checkpoint-every", type=int, default=1,
                   metavar="K",
                   help="slice checkpoints every K slices (default 1 "
                        "— every slice boundary is crash-resumable)")
    p.add_argument("--socket", default=None, metavar="PATH",
                   help="also accept requests over an AF_UNIX "
                        "datagram socket at PATH (off by default)")
    p.add_argument("--poll", type=float, default=0.05, metavar="S",
                   help="idle loop cadence in seconds")
    p.add_argument("--until-idle", action="store_true",
                   help="exit once every request is terminal (the "
                        "gate/CI mode); default: serve until killed — "
                        "the journal makes that safe at any instant")
    p.add_argument("--max-seconds", type=float, default=None,
                   metavar="S",
                   help="stop serving after S wall seconds")
    p.add_argument("--metrics-port", type=int, default=None,
                   metavar="PORT",
                   help="serve a read-only /metrics (Prometheus text) "
                        "+ /metrics.json endpoint on loopback at PORT "
                        "(0 = ephemeral; off by default)")
    p.add_argument("--metrics-every", type=float, default=2.0,
                   metavar="S",
                   help="atomic metrics-snapshot cadence under "
                        "<root>/metrics/<proc>/ (default 2)")
    p.add_argument("--slo-objective", type=float, default=0.99,
                   metavar="F",
                   help="deadline-SLO good-fraction target driving "
                        "the burn-rate alerts (default 0.99)")
    p.add_argument("--pipeline", action="store_true",
                   help="pipelined slice loop (ISSUE 19): keep "
                        "--pipeline-depth slices in flight, donate the "
                        "state buffer into each dispatch, gather only "
                        "finished lanes, and overlap all host/IO work "
                        "with device compute")
    p.add_argument("--pipeline-depth", type=int, default=2,
                   metavar="D",
                   help="in-flight slice bound for --pipeline "
                        "(default 2)")
    p.add_argument("--donate", dest="donate", default=None,
                   action="store_true",
                   help="donate the ensemble state operand (in-place "
                        "HBM update, no second (B,*grid) buffer); "
                        "default: on with --pipeline, off without")
    p.add_argument("--no-donate", dest="donate", action="store_false",
                   help="force the undonated dispatch (the "
                        "bit-exactness reference)")
    p.add_argument("--group-commit-ms", type=float, default=0.0,
                   metavar="MS",
                   help="journal group commit: batch records per fsync "
                        "under this latency window; acks wait for the "
                        "commit barrier (0 = fsync per record, the "
                        "default)")
    p.add_argument("--no-prewarm", dest="prewarm",
                   action="store_false", default=True,
                   help="disable the speculative AOT prewarm of the "
                        "likely next coalesce key")
    p.add_argument("--http-port", type=int, default=None,
                   metavar="PORT",
                   help="HTTP ingestion adapter on loopback: POST "
                        "/requests submits via the spool protocol, GET "
                        "/requests/<id>[/result[.bin]] reads status/"
                        "results (0 = ephemeral; off by default)")
    p.add_argument("--verify", action="store_true",
                   help="no daemon: replay the request journal, print "
                        "the state table, and exit nonzero when it "
                        "does not linearize against the request "
                        "transition table")
    p.add_argument("--require-complete", action="store_true",
                   help="with --verify: also fail when any submitted "
                        "request never reached done/failed/shed, or "
                        "the journal has torn lines — the "
                        "serve_gate.sh assertion")
    p.add_argument("--no-lease", action="store_true",
                   help="skip the single-writer lease (testing only: "
                        "two servers on one root WILL double-serve "
                        "requests and interleave journal appends)")
    p.add_argument("--drain", action="store_true",
                   help="no daemon: signal the live lease holder on "
                        "--root to drain (stop admission, park the "
                        "in-flight batch at the next slice boundary, "
                        "journal a clean shutdown, release the lease) "
                        "and return; exit 1 when no live holder")
    p.add_argument("--best-effort", action="store_true",
                   help="do not cancel past-deadline requests at "
                        "slice boundaries; deadlines stay advisory "
                        "(ordering + SLO accounting only)")
    p.add_argument("--hang-budget", type=float, default=None,
                   metavar="S",
                   help="fixed wall-clock budget per non-first slice; "
                        "beyond it the dispatch is declared hung and "
                        "the batch evacuated (default: adaptive, "
                        "rolling-median x --hang-multiplier)")
    p.add_argument("--hang-multiplier", type=float, default=8.0,
                   metavar="X",
                   help="adaptive hung-dispatch budget: rolling median "
                        "slice wall time times X (default 8)")
    p.set_defaults(fn=run_serve_requests)


def configure_request(p: argparse.ArgumentParser) -> None:
    p.add_argument("--root", required=True, metavar="DIR")
    p.add_argument("--request-id", default=None,
                   help="stable id (default: generated); also the "
                        "request's directory under <root>/requests/")
    p.add_argument("--model", required=True,
                   help="registry family name (diffusion/burgers/adr)")
    p.add_argument("--n", type=int, nargs="+", default=[32, 32],
                   metavar="N", help="grid sizes, physical order")
    p.add_argument("--lengths", type=float, nargs="+", default=[],
                   metavar="L", help="domain extents, physical order")
    p.add_argument("--t-end", type=float, default=0.2,
                   help="simulated-time horizon (default 0.2)")
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "float64", "bfloat16"])
    p.add_argument("--precision", default="native",
                   choices=["native", "bf16"])
    p.add_argument("--impl", default="xla",
                   help="kernel rung (xla/pallas/.../auto; part of "
                        "the coalesce key)")
    p.add_argument("--req-mesh", default="", metavar="SPEC",
                   help="require the server to run this mesh spec "
                        "(default: accept whatever it runs)")
    p.add_argument("--operand", action="append", default=[],
                   metavar="NAME=VALUE",
                   help="member-varying scalar override (e.g. "
                        "diffusivity=0.5); repeatable")
    p.add_argument("--ic", default=None,
                   help="initial-condition name override")
    p.add_argument("--ic-param", action="append", default=[],
                   metavar="NAME=VALUE",
                   help="IC parameter override; repeatable")
    p.add_argument("--t0", type=float, default=None,
                   help="initial simulated time override")
    p.add_argument("--priority", type=int, default=0,
                   help="higher coalesces/marches first; a strictly "
                        "higher arrival preempts a running batch at "
                        "the next slice boundary")
    p.add_argument("--deadline", type=float, default=None, metavar="S",
                   help="SLO: seconds from admission; drives the "
                        "deadline-aware batch ordering")
    p.add_argument("--max-retries", type=int, default=1,
                   help="crash-resume budget (default 1)")
    p.add_argument("--socket", default=None, metavar="PATH",
                   help="submit over the server's AF_UNIX socket "
                        "instead of the spool file")
    p.add_argument("--wait", type=float, default=None, metavar="S",
                   help="poll the request's verdict.json until it is "
                        "terminal (or S seconds pass; exit 3 on "
                        "timeout, 1 on failed, 75 on shed)")
    p.set_defaults(fn=run_request)


def _kv_floats(items, flag: str) -> dict:
    out = {}
    for item in items:
        key, sep, val = item.partition("=")
        if not sep or not key:
            raise SystemExit(f"{flag} wants NAME=VALUE, got {item!r}")
        out[key] = float(val)
    return out


def run_serve_requests(args) -> None:
    from multigpu_advectiondiffusion_tpu.service.journal import (
        Journal,
        JournalSchemaError,
        schema_stamps,
        verify_records,
    )
    from multigpu_advectiondiffusion_tpu.service.lease import (
        EXIT_LEASE_HELD,
        LeaseHeldError,
        inspect_lease,
    )
    from multigpu_advectiondiffusion_tpu.service.requests import (
        ALLOWED_REQUEST_TRANSITIONS,
        REQUEST_TERMINAL_STATES,
        RequestQueue,
    )

    if args.drain:
        import signal

        info = inspect_lease(args.root)
        if not info.get("present") or not info.get("alive"):
            print(f"-- drain: no live lease holder on {args.root}"
                  + (" (stale lease on disk)" if info.get("stale")
                     else ""),
                  file=sys.stderr)
            raise SystemExit(1)
        pid = int(info["holder"]["pid"])
        os.kill(pid, signal.SIGTERM)
        print(f"-- drain: SIGTERM sent to lease holder pid {pid} "
              f"(age {info.get('age_s', 0.0):.1f}s); it will stop "
              f"admission, park in-flight work at the next slice "
              f"boundary, journal a clean shutdown and release the "
              f"lease")
        return None

    if args.verify:
        journal_path = os.path.join(args.root, "journal.jsonl")
        try:
            records, torn = Journal.replay(journal_path)
        except JournalSchemaError as err:
            print(f"   PROBLEM: {err}", file=sys.stderr)
            raise SystemExit(1)
        problems = verify_records(
            records, torn=torn,
            allowed_transitions=ALLOWED_REQUEST_TRANSITIONS,
            terminal_states=REQUEST_TERMINAL_STATES,
            initial_state="received",
            require_complete=args.require_complete,
            schema_versions=schema_stamps(journal_path),
        )
        with Journal(journal_path, fsync=False) as j:
            q, report = RequestQueue.replay(j)
        print(f"-- journal {journal_path}: {len(records)} record(s), "
              f"{torn} torn line(s), {len(q.requests)} request(s)")
        for rec in sorted(q.requests.values(), key=lambda r: r.order):
            print(f"   {rec.request_id:<24} {rec.state:<10} "
                  f"attempts={rec.attempts} slices={rec.slices} "
                  f"failures={len(rec.failures)}")
        for msg in report.get("problems", []):
            problems.append(f"replay: {msg}")
        for msg in problems:
            print(f"   PROBLEM: {msg}", file=sys.stderr)
        if problems:
            raise SystemExit(1)
        print("-- request journal linearizes")
        return None

    from multigpu_advectiondiffusion_tpu.service.server import (
        RequestServer,
    )

    try:
        server = RequestServer(
            args.root,
            max_batch=args.max_batch,
            slice_steps=args.slice_steps,
            queue_bound=args.queue_bound,
            retry_after_s=args.retry_after,
            mesh=args.mesh,
            mem_budget_bytes=args.mem_budget_mb * (1 << 20),
            checkpoint_every=args.checkpoint_every,
            socket_path=args.socket,
            metrics_port=args.metrics_port,
            metrics_every_s=args.metrics_every,
            slo_objective=args.slo_objective,
            pipeline=args.pipeline,
            pipeline_depth=args.pipeline_depth,
            donate=args.donate,
            group_commit_s=args.group_commit_ms / 1000.0,
            prewarm=args.prewarm,
            http_port=args.http_port,
            lease=not args.no_lease,
            best_effort=args.best_effort,
            hang_budget_s=args.hang_budget,
            hang_multiplier=args.hang_multiplier,
        )
    except LeaseHeldError as err:
        print(f"-- serve-requests: {err}", file=sys.stderr)
        raise SystemExit(EXIT_LEASE_HELD)
    if server.metrics_port is not None:
        print(f"-- metrics endpoint: "
              f"http://127.0.0.1:{server.metrics_port}/metrics")
    if server.http_port is not None:
        print(f"-- request endpoint: "
              f"http://127.0.0.1:{server.http_port}/requests")
    try:
        outcome = server.serve(
            until_idle=args.until_idle,
            max_seconds=args.max_seconds,
            poll_seconds=args.poll,
        )
    finally:
        server.close()
    states = outcome.get("states", {})
    print(f"-- serve-requests: {outcome.get('reason')}; "
          + ", ".join(f"{k}={v}" for k, v in sorted(states.items())))
    if outcome.get("reason") == "stalled":
        raise SystemExit(2)
    return None


def run_request(args) -> None:
    import json
    import time

    from multigpu_advectiondiffusion_tpu.service.requests import (
        RequestSpec,
        new_request_id,
        request_dir,
        submit_request_to_spool,
    )

    spec = RequestSpec(
        request_id=args.request_id or new_request_id(),
        model=args.model,
        n=list(args.n),
        lengths=list(args.lengths),
        t_end=args.t_end,
        dtype=args.dtype,
        precision=args.precision,
        impl=args.impl,
        mesh=args.req_mesh,
        operands=_kv_floats(args.operand, "--operand"),
        ic=args.ic,
        ic_params=_kv_floats(args.ic_param, "--ic-param"),
        t0=args.t0,
        priority=args.priority,
        deadline_s=args.deadline,
        max_retries=args.max_retries,
    )
    if args.socket:
        from multigpu_advectiondiffusion_tpu.service.server import (
            submit_request_over_socket,
        )

        submit_request_over_socket(args.socket, spec)
        print(f"-- submitted {spec.request_id} over {args.socket}")
    else:
        path = submit_request_to_spool(args.root, spec)
        print(f"-- submitted {spec.request_id} "
              f"(priority {spec.priority}) -> {path}")
    if args.wait is None:
        return None
    verdict_path = os.path.join(
        request_dir(args.root, spec.request_id), "verdict.json"
    )
    deadline = time.monotonic() + args.wait
    while time.monotonic() < deadline:
        try:
            with open(verdict_path) as f:
                verdict = json.load(f)
        except (OSError, ValueError):
            time.sleep(0.05)
            continue
        print(json.dumps(verdict, sort_keys=True))
        status = verdict.get("status")
        if status == "failed":
            raise SystemExit(1)
        if status == "shed":
            raise SystemExit(75)
        return None
    print(f"-- no verdict for {spec.request_id} within {args.wait}s",
          file=sys.stderr)
    raise SystemExit(3)


def run_submit(args) -> None:
    from multigpu_advectiondiffusion_tpu.service.queue import (
        JobSpec,
        new_job_id,
        submit_to_spool,
    )

    argv = list(args.argv)
    if argv and argv[0] == "--":
        argv = argv[1:]
    env = {}
    for item in args.env:
        key, _, val = item.partition("=")
        env[key] = val
    spec = JobSpec(
        job_id=args.job_id or new_job_id(),
        argv=argv,
        priority=args.priority,
        max_retries=args.max_retries,
        devices=args.devices,
        mesh_template=args.mesh_template,
        env=env,
    )
    path = submit_to_spool(args.root, spec)
    print(f"-- submitted {spec.job_id} (priority {spec.priority}) "
          f"-> {path}")
    return None


def configure_migrate(p: argparse.ArgumentParser) -> None:
    p.add_argument("--root", required=True, metavar="DIR",
                   help="service root whose journal.jsonl to upgrade "
                        "in place (atomic: tempfile + rename) to the "
                        "current schema version")
    p.set_defaults(fn=run_migrate)


def run_migrate(args) -> None:
    from multigpu_advectiondiffusion_tpu import telemetry
    from multigpu_advectiondiffusion_tpu.service.journal import (
        JournalSchemaError,
        migrate_journal,
    )

    path = os.path.join(args.root, "journal.jsonl")
    try:
        report = migrate_journal(path)
    except FileNotFoundError:
        print(f"-- migrate: no journal at {path}", file=sys.stderr)
        raise SystemExit(1)
    except JournalSchemaError as err:
        print(f"-- migrate: {err}", file=sys.stderr)
        raise SystemExit(1)
    telemetry.event(
        "journal", "migrate",
        path=path,
        migrated=report["migrated"],
        from_schema=report["from_schema"],
        schema=report["schema"],
        records=report["records"],
    )
    if report["migrated"]:
        print(f"-- journal {path}: schema {report['from_schema']} -> "
              f"{report['schema']} ({report['records']} record(s), "
              f"{report['torn']} torn line(s) preserved)")
    else:
        print(f"-- journal {path}: already schema "
              f"{report['schema']}, nothing to do")
    return None
