"""Event-schema registry: the single source of truth for what the
telemetry stream may contain.

Every event kind (and, where the set is closed, every event name) the
instrumented layers emit is registered here with its required fields.
Two consumers:

* :func:`validate_event` — structural validation of a live/loaded
  event (the analyzer and tests run it over real streams);
* :func:`scan_emitted` — a *static* scan of the package source for
  ``telemetry.event(<kind>, <name>, ...)`` / ``sink.event(...)`` /
  ``.counter(<name>, ...)`` call sites, so a tier-1 test
  (tests/test_schema.py) fails the moment someone emits a kind or name
  this registry (or README's event table) does not know — the guard
  against silent schema drift.

``None`` as a name key is the wildcard: the kind carries open-ended
names (``summary`` events are named after the run, ``crash`` events
after the exception type, ``tune`` names arrive via a variable).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Set, Tuple

# kind -> name -> required fields (beyond the sink's own t/proc/kind/
# name envelope). name None = wildcard for that kind.
EVENT_REGISTRY: Dict[str, Dict[Optional[str], Set[str]]] = {
    "meta": {"open": {"schema", "wall_time"}},
    "sink": {"rotate": {"schema", "wall_time", "previous",
                        "rotated_bytes"}},
    "span": {None: {"phase", "id", "depth"}},
    "counter": {None: {"inc", "total"}},
    "dispatch": {
        "build": {"key", "impl"},
        # hung-dispatch watchdog (ISSUE 20): a slice blew its
        # wall-clock budget — the batch is evacuated from its last
        # slice checkpoints and bisected until the poison member is
        # isolated and quarantined
        "hung": {"batch", "slice", "elapsed_s", "budget_s", "jobs"},
    },
    # solver-plugin registry (models/registry.py, ISSUE 15): CLI
    # --model resolution through the registry — one event per resolved
    # run naming the family and the generated subcommand
    "model": {"resolve": {"model", "ndim", "command"}},
    "ladder": {"degrade": {"from", "to", "reason"}},
    "physics": {"probe": {"step", "time"}},
    # in-situ physics diagnostics (diagnostics/physics.py via the
    # supervisor's --diag-every cadence): the fused observable suite
    # and tolerance-rule breaches
    "phys": {
        "diag": {"step", "time", "solver"},
        "violation": {"step", "time", "rule", "message", "tolerance"},
    },
    # the science regression gate's verdict (diagnostics/compare.py)
    "science": {"gate": {"ok", "regressions", "rows"}},
    # low-precision storage rung (models/base._validate_precision,
    # ISSUE 16): one event per solver constructed with
    # precision='bf16' — records the storage/compute dtype split and
    # whether the generic loop's compensation carry is armed
    # (core.dtypes.bf16_carry_enabled), so a carry-off run is visible
    # in the stream, not just in its error norms
    "precision": {
        "engage": {"storage_dtype", "compute_dtype", "carry"},
    },
    "resilience": {
        "sentinel_armed": {"cadence", "growth"},
        "rollback": {"retry", "step", "rollback_to_it", "action"},
        "retries_exhausted": {"step", "retries"},
        "preempt": {"step"},
        "agree": {"tag", "values"},
        "elastic_resume": {"checkpoint", "saved_processes", "processes"},
        # dt-backoff inheritance (--dt-scale): a scheduler-retried job
        # starts at the reduced step its failed attempt backed off to
        "dt_inherit": {"factor", "action"},
    },
    "rank": {
        "watchdog_armed": {"timeout", "interval", "processes"},
        "failure": {"reason", "exit_code"},
    },
    "sdc": {"detect": {"step", "mismatched_cells"}},
    "io": {
        "checkpoint_write": {"path", "bytes", "seconds"},
        "binary_write": {"path", "bytes", "seconds"},
        # SnapshotStreamer publishes (utils/io.py): downsampled field
        # snapshots, atomic + rotation-capped
        "snapshot_write": {"path", "bytes", "seconds", "iteration",
                           "stride"},
    },
    "dist_init": {
        "attempt": {"attempt", "attempts"},
        "retry": {"attempt", "backoff_seconds"},
        "ok": {"attempt"},
        "failed": {"attempts", "error"},
    },
    "sync": {"barrier": {"tag"}},
    # in-kernel remote-DMA halo exchange (ops/pallas/fused_slab_run
    # exchange='dma', recorded by parallel/halo.record_remote_dma): one
    # event per traced run call — the sharded whole-run program moves
    # its ghost rows over ICI from inside the Pallas kernel, so this
    # (plus the halo.dma_bytes_per_execution counter) is the ONLY
    # telemetry trace of that communication
    "halo": {
        "in_kernel": {"kernel", "axis", "depth", "blocks",
                      "bytes_per_execution"},
    },
    "tune": {
        "lookup": set(),
        "candidates": set(),
        "measure": set(),
        "decision": set(),
        "fallback": set(),
        None: set(),
    },
    # batched ensemble engine (models/base.run_ensemble /
    # advance_to_ensemble): one event per batched dispatch, carrying
    # the member count, the inner stepper (vmapped or B-folded), and —
    # since the mesh-scale round — the device placement (devices,
    # member_sharding, mesh), so a batched dispatch that silently fell
    # back to one device is visible in the stream
    "ensemble": {
        "dispatch": {"members", "stepper", "devices", "member_sharding"},
    },
    # persistent AOT executable cache (tuning/aot_cache.py): every
    # lookup is a hit or a (reasoned) miss, every write a store —
    # out/ensemble_gate.sh gates the warm-run hit on these
    "aot_cache": {
        "hit": {"key", "compile_seconds_saved"},
        "miss": {"key", "reason"},
        "store": {"key", "persisted"},
    },
    "progress": {"chunk": {"step", "steps_done", "step_seconds"}},
    "perf": {
        "outlier": {"step", "step_seconds", "median", "threshold"},
        "histogram": {"edges", "counts", "chunks"},
    },
    "summary": {None: {"seconds", "mlups"}},
    # measured introspection (telemetry/xprof.py): per-executable XLA
    # cost/memory capture at dispatch, and the per-run measured-vs-
    # modeled reconciliation
    "xla": {
        "cost": {"key", "flops", "bytes_accessed", "compile_seconds"},
        "measured": {"run", "xla_bytes_per_step", "xla_flops_per_step"},
    },
    # chunk-cadence device-memory watermarks (device.memory_stats or
    # the live-arrays census fallback)
    "mem": {"watermark": {"bytes_in_use", "peak_bytes", "source"}},
    # measured-peak calibration writes (telemetry/calibration.py)
    "calib": {"update": {"device_kind", "path", "persisted"}},
    # checkify sanitizer trips (analysis/sanitizer.py, --checkify): one
    # event per caught NaN/div0/OOB, before SanitizerError enters the
    # supervisor's rollback path
    "sanitizer": {"trip": {"message", "errors"}},
    # crash-safe multi-run scheduler (service/daemon.py): the daemon's
    # own decisions, streamed to <root>/sched_events.jsonl — recovery
    # replays, admission verdicts (warm/deferred), priority
    # preemptions, classified retries, journal-degradation warnings
    "sched": {
        "start": {"root", "max_concurrent", "device_budget"},
        "recover": {"records", "torn_lines", "jobs", "adopted",
                    "requeued", "completed"},
        "admit": {"job", "granted_devices", "warm"},
        "defer": {"job", "reason"},
        "preempt": {"victim", "for_job", "blocked"},
        "retry": {"job", "attempt", "policy", "dt_scale"},
        "adopt": {"job", "pid"},
        "journal_degraded": {"pending"},
        # hardened spool ingest (service/queue.ingest_spool): a torn or
        # corrupt mailbox entry is quarantined and reported, never fatal
        "spool_skip": {"file", "error"},
        "stop": {"reason", "states"},
    },
    # continuous-batching request server (service/server.py, ISSUE 17):
    # the daemon's own decisions, streamed to <root>/serve_events.jsonl
    # — recovery replays, per-request admission/shed verdicts, batch
    # formation, slice progress (the request timeline's spine), joins,
    # preemptions, member-attributed divergence, spool quarantines
    "serve": {
        "start": {"root", "max_batch", "slice_steps", "queue_bound",
                  "pipeline", "pipeline_depth", "donate",
                  "group_commit_s"},
        "recover": {"records", "torn_lines", "requests", "requeued",
                    "failed", "clean_shutdown"},
        "admit": {"job", "key", "warm"},
        "defer": {"job", "reason"},
        "shed": {"job", "open", "bound", "retry_after_s"},
        "batch": {"batch", "key", "members", "lanes"},
        # pipelined slices (ISSUE 19) additionally carry
        # stall_seconds / overlap_fraction / depth — optional here
        # because the synchronous loop's slices do not
        "slice": {"batch", "slice", "active", "done", "occupancy",
                  "seconds"},
        "join": {"batch", "waiting"},
        "preempt": {"batch", "for_job", "parked"},
        "divergence": {"batch", "jobs"},
        "spool_skip": {"file", "error"},
        "stop": {"reason", "states"},
        # stdlib HTTP ingestion adapter came up (service/http.py)
        "http": {"port"},
    },
    # zero-copy pipelined serving (ISSUE 19, service/server.py): the
    # overlap machinery's own trace — dispatch-ahead depth, the
    # non-blocking publish of finished lanes, every stall the pipeline
    # could not hide, speculative AOT prewarm verdicts, and the
    # per-batch device-idle accounting the bench's device_idle_frac
    # column and the serving perf gate read
    "pipeline": {
        "dispatch": {"batch", "slice", "depth"},
        "publish": {"batch", "slice", "lanes", "wait_seconds"},
        "stall": {"batch", "where", "seconds"},
        "prewarm": {"key", "status", "seconds"},
        "batch_idle": {"batch", "idle_fraction", "busy_seconds",
                       "wall_seconds", "slices"},
    },
    # per-request lifecycle in the server's stream: every journal
    # transition is mirrored as a req:state event so tpucfd-trace can
    # render the request timeline without reading the journal.
    # req:done/req:failed additionally carry deadline_s (optional —
    # only when the request declared one) so the metrics replay
    # adapter and offline SLO evaluation see the same verdicts the
    # live SloTracker saw
    "req": {
        "submit": {"job", "priority"},
        "state": {"job", "from", "to"},
        "done": {"job", "seconds", "slices"},
        "failed": {"job", "reason"},
        # deadline enforcement (ISSUE 20): a past-deadline request
        # cancelled at a slice boundary (its lane frozen, the rest of
        # the batch unperturbed); suppressed under --best-effort
        "deadline_cancel": {"job", "deadline_s", "elapsed_s"},
    },
    # single-writer lease (service/lease.py, ISSUE 20): exactly one
    # daemon per service root — acquisition (takeover=True when a
    # stale lease from a dead holder was reclaimed), the takeover's
    # forensics, and the release on clean shutdown/drain
    "lease": {
        "acquire": {"pid", "path", "takeover"},
        "takeover": {"pid", "prev_pid", "age_s"},
        "release": {"pid"},
    },
    # graceful drain & handover (ISSUE 20): admission stops, the
    # in-flight batch parks at its next slice boundary, the journal
    # gets the clean-shutdown marker, the lease releases — the
    # successor starts with zero replay-recovery work
    "drain": {
        "start": {"reason", "open"},
        "parked": {"batch", "members"},
        "done": {"clean", "open"},
    },
    # journal schema migration (service/journal.migrate_journal via
    # the ``migrate`` CLI verb, ISSUE 20)
    "journal": {
        "migrate": {"path", "migrated", "from_schema", "schema",
                    "records"},
    },
    # per-job lifecycle in the scheduler's stream, namespaced by job
    # id: every journal transition is mirrored as a job:state event so
    # tpucfd-trace can render the queue timeline without reading the
    # journal
    "job": {
        "submit": {"job", "priority"},
        "state": {"job", "from", "to"},
        "start": {"job", "attempt"},
        "exit": {"job", "rc", "seconds"},
    },
    # fleet metrics (telemetry/metrics.py, ISSUE 18): periodic atomic
    # registry snapshots (JSON + Prometheus text under a per-process
    # snapshot dir) and the server's read-only /metrics HTTP endpoint
    "metrics": {
        "snapshot": {"dir", "counters", "gauges", "histograms"},
        "serve": {"port"},
    },
    # SLO burn-rate engine (telemetry/metrics.SloTracker): multi-window
    # deadline-SLO evaluation over req:done/req:failed verdicts — an
    # alert on crossing a window's burn-rate threshold, a resolve when
    # every window clears; the request server also journals both as
    # note records so they survive the process
    "slo": {
        "alert": {"slo", "objective", "window_s", "burn_rate",
                  "threshold", "bad", "total"},
        "resolve": {"slo", "objective", "burn_rate"},
    },
    # tpucfd-status dashboard (cli/status.py): one event per rendered
    # frame when the status verb itself runs with --metrics
    "status": {"render": {"root", "requests", "jobs"}},
    "crash": {None: {"message"}},
}


def validate_event(ev: dict) -> List[str]:
    """Structural problems with one event dict (empty list = valid)."""
    problems = []
    for key in ("t", "proc", "kind", "name"):
        if key not in ev:
            problems.append(f"missing envelope field {key!r}")
    kind = ev.get("kind")
    if kind not in EVENT_REGISTRY:
        problems.append(f"unregistered kind {kind!r}")
        return problems
    names = EVENT_REGISTRY[kind]
    name = ev.get("name")
    if name in names:
        required = names[name]
    elif None in names:
        required = names[None]
    else:
        problems.append(f"unregistered name {name!r} for kind {kind!r}")
        return problems
    for field in required:
        if field not in ev:
            problems.append(f"{kind}:{name} missing field {field!r}")
    return problems


# Counter names the instrumented layers emit (halo.py).
COUNTER_NAMES: Set[str] = {
    "halo.exchanges_traced",
    "halo.bytes_per_execution",
    # in-kernel remote-DMA bytes (halo.record_remote_dma): the dma
    # rung's ICI payload per compiled execution, blocks folded in
    "halo.dma_bytes_per_execution",
    # fleet-metrics monotonic counters (telemetry/metrics.py, ISSUE
    # 18): the MetricsRegistry vocabulary the serving/scheduler hot
    # paths increment and the replay adapter re-derives — registered
    # here so the same drift guard covers both emission surfaces
    "serve_requests_received_total",
    "serve_requests_admitted_total",
    "serve_requests_done_total",
    "serve_requests_failed_total",
    "serve_requests_shed_total",
    "serve_requests_requeued_total",
    "serve_batches_formed_total",
    "serve_slices_total",
    "serve_deadline_met_total",
    "serve_deadline_missed_total",
    "serve_slo_alerts_total",
    "serve_slo_resolves_total",
    # zero-copy pipelined serving (ISSUE 19): dispatch-ahead launches,
    # and the speculative AOT prewarm's attempts/deserialization hits
    "serve_pipeline_dispatches_total",
    "serve_prewarm_total",
    "serve_prewarm_hits_total",
    # operational hardening (ISSUE 20): stale-lease takeovers, batches
    # parked by a graceful drain, hung-dispatch declarations, and
    # deadline cancellations at slice boundaries
    "serve_lease_takeovers_total",
    "serve_drain_parked_total",
    "serve_dispatch_hung_total",
    "serve_deadline_cancelled_total",
    "sched_jobs_submitted_total",
    "sched_jobs_admitted_total",
    "sched_job_exits_total",
    "sched_retries_total",
    "sched_preemptions_total",
}

def scan_emitted(
    root: Optional[str] = None,
) -> Tuple[Set[Tuple[str, Optional[str]]], Set[str]]:
    """Statically scan the package source for emission sites. Returns
    ``(event_pairs, counter_names)`` where each pair is
    ``(kind, name-or-None)`` — name ``None`` when the call site passes
    a variable. Test files are out of scope (they emit arbitrary
    events on purpose).

    Implemented on the shared AST rule engine
    (``analysis/rules.scan_emission_sites`` — the generalization of the
    regex scanner that used to live here): same contract, and the same
    extraction the ``unregistered-emission`` lint rule runs per module,
    so the tier-1 schema test and ``tpucfd-check`` cannot disagree
    about what counts as an emission site."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from multigpu_advectiondiffusion_tpu.analysis.rules import (
        scan_emission_sites,
    )

    return scan_emission_sites(root)


def registered(kind: str, name: Optional[str]) -> bool:
    """True when the (kind, name) pair — name possibly unknown — is
    covered by the registry."""
    names = EVENT_REGISTRY.get(kind)
    if names is None:
        return False
    if name is None:
        return True  # dynamic name: the kind itself is the contract
    return name in names or None in names
