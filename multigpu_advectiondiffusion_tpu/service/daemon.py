"""The crash-safe multi-run scheduler daemon (ISSUE 14, ROADMAP item 5).

One long-lived process multiplexes a journaled queue of run requests
onto the device budget, reusing the one-shot CLI as its worker binary —
every resilience property the batch machinery already proves (atomic
CRC checkpoints, ``--resume auto``, preemption-safe exit 75, elastic
resharded resume, the rank watchdog, the AOT executable cache) becomes
a scheduling primitive:

* **crash safety** — every state transition is a write-ahead journal
  commit (``service/journal.py``); SIGKILL the daemon at any instant,
  restart it, and :meth:`Scheduler.recover` replays the journal,
  re-adopts still-alive job processes (or classifies dead ones by
  their artifacts) and requeues in-flight work for ``--resume auto``
  recovery — the queue completes bit-exact vs an uninterrupted run;
* **per-job namespacing** — each job owns ``<root>/jobs/<id>/``
  (checkpoints, telemetry, snapshots, heartbeats all keyed by job id),
  so concurrent or serial jobs can never adopt each other's
  checkpoints;
* **admission control** — measured memory watermarks + AOT-warm
  admission (``service/admission.py``);
* **priority preemption** — a higher-priority arrival SIGTERMs the
  lowest-priority running job; the existing preemption path checkpoints
  it and exits 75, the scheduler requeues it, and it resumes
  elastically on whatever device slice is free at re-admission;
* **bounded retries** — failed attempts are classified
  (divergence / SDC / rank failure / disk-full / generic) into
  distinct policies; divergence inherits the dt backoff across
  attempts (``--dt-scale``), disk-full retries exactly once, and every
  attempt lands in the job's journaled failure ledger.

Jobs run as child processes with ``PR_SET_PDEATHSIG`` (Linux): the
daemon's death kills its workers, so recovery never races a live
orphan writing the job directory; where pdeathsig is unavailable the
recovery path re-adopts live orphans by pid + cmdline instead.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

from multigpu_advectiondiffusion_tpu.service.admission import (
    AdmissionController,
    WarmLedger,
    warm_key,
)
from multigpu_advectiondiffusion_tpu.service.journal import Journal
from multigpu_advectiondiffusion_tpu.service.queue import (
    JobQueue,
    JobRecord,
    JobSpec,
    ingest_spool,
)

#: exit-code vocabulary the workers already document (README table)
EXIT_PREEMPTED = 75
EXIT_RANK_FAILURE = 76
EXIT_SDC = 77

#: structured-error type names classified as divergence (the family
#: rooted at SolverDivergedError whose retry wants a smaller dt)
_DIVERGED_TYPES = frozenset({
    "SolverDivergedError", "PhysicsViolationError", "SanitizerError",
    "EnsembleMemberDivergedError",
})

#: retry policies per failure class: ``budget`` None = the spec's
#: max_retries; ``dt_backoff`` multiplies the inherited --dt-scale
RETRY_POLICIES = {
    "diverged": {"dt_backoff": True, "budget": None},
    "sdc": {"dt_backoff": False, "budget": None},
    "rank_failure": {"dt_backoff": False, "budget": None},
    "disk_full": {"dt_backoff": False, "budget": 1},
    "error": {"dt_backoff": False, "budget": None},
}


# --------------------------------------------------------------------- #
# argv helpers
# --------------------------------------------------------------------- #
def _flag_value(argv: List[str], flag: str) -> Optional[str]:
    for i, a in enumerate(argv):
        if a == flag and i + 1 < len(argv):
            return argv[i + 1]
    return None


def _set_flag(argv: List[str], flag: str, value: str) -> List[str]:
    out = list(argv)
    for i, a in enumerate(out):
        if a == flag and i + 1 < len(out):
            out[i + 1] = value
            return out
    return out + [flag, value]


def _ckpt_iteration(path: str) -> Optional[int]:
    stem = os.path.basename(path)
    if not stem.startswith("checkpoint_"):
        return None
    stem = stem[len("checkpoint_"):].rsplit(".", 1)[0]
    return int(stem) if stem.isdigit() else None


# --------------------------------------------------------------------- #
# Worker runners
# --------------------------------------------------------------------- #
def _load_libc():
    """Resolve libc BEFORE any fork: the preexec hook runs between
    fork and exec inside a threaded (JAX) parent, where an import or
    dlopen could deadlock on an inherited lock — so it must only call
    an already-bound symbol."""
    try:
        import ctypes

        return ctypes.CDLL(None, use_errno=True)
    except Exception:  # noqa: BLE001 — best-effort; adoption covers it
        return None


_LIBC = _load_libc()


def _pdeathsig_preexec():  # pragma: no cover — runs in the child
    """Ask Linux to SIGKILL this worker when the daemon dies, closing
    the adopt-a-live-orphan race for crash recovery."""
    if _LIBC is not None:
        _LIBC.prctl(1, signal.SIGKILL)  # PR_SET_PDEATHSIG


class SubprocessHandle:
    def __init__(self, proc: subprocess.Popen, log_fh):
        self._proc = proc
        self._log_fh = log_fh
        self.pid = proc.pid

    def poll(self) -> Optional[int]:
        return self._proc.poll()

    def terminate(self) -> None:
        if self._proc.poll() is None:
            self._proc.terminate()

    def kill(self) -> None:
        if self._proc.poll() is None:
            self._proc.kill()

    def close(self) -> None:
        try:
            self._log_fh.close()
        except OSError:
            pass


class SubprocessRunner:
    """Default runner: one CLI process per job attempt (the reference's
    one-binary-per-run shape, now multiplexed by the daemon)."""

    def __init__(self, python: Optional[str] = None,
                 pdeathsig: bool = True):
        self.python = python or sys.executable
        self.pdeathsig = pdeathsig and sys.platform.startswith("linux")

    def start(self, argv: List[str], env: Dict[str, str],
              log_path: str) -> SubprocessHandle:
        pkg_dir = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        repo = os.path.dirname(pkg_dir)
        merged = dict(os.environ)
        merged.update(env)
        merged["PYTHONPATH"] = os.pathsep.join(
            [repo] + ([merged["PYTHONPATH"]]
                      if merged.get("PYTHONPATH") else [])
        )
        log_fh = open(log_path, "a")
        proc = subprocess.Popen(
            [self.python, "-m", "multigpu_advectiondiffusion_tpu.cli",
             *argv],
            stdout=log_fh, stderr=subprocess.STDOUT, env=merged,
            preexec_fn=_pdeathsig_preexec if self.pdeathsig else None,
        )
        return SubprocessHandle(proc, log_fh)


class FinishedHandle:
    """A handle whose work already ran (in-process runner) or whose
    outcome is already known (artifact classification)."""

    def __init__(self, rc: int, pid: Optional[int] = None):
        self._rc = int(rc)
        self.pid = pid

    def poll(self) -> int:
        return self._rc

    def terminate(self) -> None:
        pass

    def kill(self) -> None:
        pass

    def close(self) -> None:
        pass


class InProcessRunner:
    """Test-grade runner: executes the CLI in this process (no
    subprocess cost, no preemption concurrency). Structured failures
    land in ``<job>/crash.json`` for the classifier, mirroring the
    crash event the subprocess excepthook would have streamed."""

    def start(self, argv: List[str], env: Dict[str, str],
              log_path: str) -> FinishedHandle:
        del env  # in-process: the test harness owns the environment
        from multigpu_advectiondiffusion_tpu.cli.__main__ import main

        job_dir = _flag_value(argv, "--save") or "."
        try:
            rv = main(list(argv))
            rc = 0 if rv is not False else 1
        except SystemExit as exc:
            rc = int(exc.code or 0) if not isinstance(exc.code, str) else 1
        except KeyboardInterrupt:
            raise
        except BaseException as exc:  # noqa: BLE001 — classified below
            memo = {
                "type": type(exc).__name__,
                "message": str(exc)[:500],
                "errno": getattr(exc, "errno", None),
            }
            from multigpu_advectiondiffusion_tpu.utils.io import (
                atomic_write_text,
            )

            atomic_write_text(
                os.path.join(job_dir, "crash.json"), json.dumps(memo)
            )
            rc = 1
        return FinishedHandle(rc)


class AdoptedHandle:
    """A still-alive worker from a previous daemon incarnation: poll
    watches the pid; once it dies the outcome is classified from the
    job directory's artifacts (a non-child cannot be waited on)."""

    def __init__(self, pid: int, job_dir: str):
        self.pid = int(pid)
        self.job_dir = job_dir

    def poll(self) -> Optional[int]:
        if _pid_alive(self.pid):
            return None
        return _artifact_rc(self.job_dir)

    def terminate(self) -> None:
        try:
            os.kill(self.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass

    def kill(self) -> None:
        try:
            os.kill(self.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    def close(self) -> None:
        pass


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


def _pid_runs_job(pid: int, job_dir: str) -> bool:
    """Guard against pid reuse before adopting: the live process's
    cmdline must mention this job's directory. Falls back to pid
    liveness where /proc is unavailable."""
    if not _pid_alive(pid):
        return False
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            cmdline = f.read().decode("utf-8", errors="replace")
    except OSError:
        return True
    return job_dir in cmdline


def _artifact_rc(job_dir: str) -> int:
    """Outcome of an attempt whose exit code was unobservable (adopted
    orphan): a published summary means success, a preemption manifest
    means exit 75, anything else is a retryable failure — ``--resume
    auto`` picks up from the checkpoints either way."""
    if os.path.exists(os.path.join(job_dir, "summary.json")):
        return 0
    if os.path.exists(os.path.join(job_dir, "preempt.json")):
        return EXIT_PREEMPTED
    return 1


def _crash_evidence(job_dir: str, tail_bytes: int = 131072) -> dict:
    """Structured failure evidence: the in-process crash memo, else the
    last ``crash`` event in the job's telemetry stream tail."""
    memo_path = os.path.join(job_dir, "crash.json")
    if os.path.exists(memo_path):
        try:
            with open(memo_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            pass
    events = os.path.join(job_dir, "events.jsonl")
    last = {}
    try:
        size = os.path.getsize(events)
        with open(events, "rb") as f:
            f.seek(max(0, size - tail_bytes))
            text = f.read().decode("utf-8", errors="replace")
    except OSError:
        return last
    for line in text.splitlines():
        if '"crash"' not in line:
            continue
        try:
            ev = json.loads(line)
        except ValueError:
            continue
        if ev.get("kind") == "crash":
            last = {"type": ev.get("name"),
                    "message": ev.get("message", "")}
    return last


def classify_failure(rc: int, job_dir: str) -> tuple:
    """Map a failed attempt to its retry policy: ``(policy, reason)``."""
    if rc == EXIT_RANK_FAILURE:
        return "rank_failure", "peer rank died or stalled (exit 76)"
    if rc == EXIT_SDC:
        return "sdc", "silent-data-corruption budget exhausted (exit 77)"
    ev = _crash_evidence(job_dir)
    etype = ev.get("type") or ""
    message = ev.get("message") or ""
    if etype in _DIVERGED_TYPES:
        return "diverged", f"{etype}: {message}"[:300]
    if etype == "SDCDetectedError":
        return "sdc", f"{etype}: {message}"[:300]
    if etype in ("OSError", "IOError") and (
        ev.get("errno") == 28 or "No space left" in message
    ):
        return "disk_full", f"{etype}: {message}"[:300]
    return "error", (f"{etype}: {message}"[:300] if etype
                     else f"exit code {rc}")


# --------------------------------------------------------------------- #
# The scheduler
# --------------------------------------------------------------------- #
class Scheduler:
    """Journal-backed multi-run scheduler; see the module docstring.

    Layout under ``root``::

        journal.jsonl        write-ahead queue journal (commit records)
        sched_events.jsonl   the daemon's own sched:*/job:* telemetry
        spool/               atomic submission mailbox
        aot/                 shared AOT executable cache (warm admission)
        jobs/<id>/           per-job namespace: checkpoints, events.jsonl,
                             job.log, snapshots, .heartbeats, results
    """

    def __init__(self, root: str, max_concurrent: int = 1,
                 device_budget: int = 1, mem_budget_bytes: int = 0,
                 poll_seconds: float = 0.2, runner=None,
                 aot_cache: bool = True, fsync: bool = True,
                 lease: bool = False, heartbeat_s: float = 2.0):
        self.max_concurrent = max(1, int(max_concurrent))
        self.runner = runner if runner is not None else SubprocessRunner()
        if (self.max_concurrent > 1
                and isinstance(self.runner, SubprocessRunner)
                and os.environ.get("JAX_PLATFORMS") != "cpu"):
            # each job is its own CLI process, and nothing tells it
            # which chips are its own: every child would claim every
            # chip, and a chip belongs to one process. (The daemon
            # itself never initializes a JAX backend, so one child at a
            # time gets the chips.)
            raise ValueError(
                f"max_concurrent={self.max_concurrent} would run that "
                "many jobs as processes that each claim every chip of "
                "this host; use max_concurrent=1, or pin the daemon "
                "and its jobs to the CPU with JAX_PLATFORMS=cpu"
            )
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        # single-writer lease (ISSUE 20): same mechanism as the request
        # server — acquire before any root artifact is opened, so a
        # second daemon on this root exits naming the holder instead of
        # interleaving journal appends
        self.lease = None
        if lease:
            from multigpu_advectiondiffusion_tpu.service.lease import (
                ServiceLease,
            )

            self.lease = ServiceLease(
                self.root, role="serve", heartbeat_s=heartbeat_s,
            ).acquire()
        self.jobs_root = os.path.join(self.root, "jobs")
        os.makedirs(self.jobs_root, exist_ok=True)
        self.aot_dir = (
            os.path.join(self.root, "aot") if aot_cache else None
        )
        if self.aot_dir:
            os.makedirs(self.aot_dir, exist_ok=True)
        self.poll_seconds = float(poll_seconds)
        from multigpu_advectiondiffusion_tpu.telemetry.sink import (
            TelemetrySink,
        )

        # a PRIVATE sink (never the module-level slot): in-process
        # workers install/uninstall their own --metrics sinks and must
        # not tear down the daemon's stream
        self._sink = TelemetrySink(
            os.path.join(self.root, "sched_events.jsonl")
        )
        if self.lease is not None:
            self._sink.event(
                "lease", "acquire", pid=os.getpid(),
                path=self.lease.path,
                takeover=self.lease.takeover is not None,
            )
            if self.lease.takeover:
                self._sink.event(
                    "lease", "takeover", pid=os.getpid(),
                    prev_pid=self.lease.takeover.get("pid"),
                    age_s=self.lease.takeover.get("age_s"),
                )
        self.journal = Journal(
            os.path.join(self.root, "journal.jsonl"), fsync=fsync
        )
        self.queue, self.replay_report = JobQueue.replay(self.journal)
        self.admission = AdmissionController(
            device_budget=device_budget,
            mem_budget_bytes=mem_budget_bytes,
            ledger=self._rebuild_ledger(),
        )
        #: job_id -> live attempt {handle, started, mesh_arg, base_it}
        self._handles: Dict[str, dict] = {}
        self._deferred: Dict[str, str] = {}
        self._recovered = False
        # fleet metrics (ISSUE 18): the scheduler's own snapshot dir,
        # per incarnation, unioned with the server's by
        # telemetry.metrics.merge_snapshot_dirs
        from multigpu_advectiondiffusion_tpu.telemetry.metrics import (
            MetricsRegistry,
        )

        self.metrics = MetricsRegistry(proc=f"daemon-{os.getpid()}")
        self.metrics_dir = os.path.join(
            self.root, "metrics", self.metrics.proc
        )
        self.metrics_every_s = 2.0
        self._last_export = 0.0
        self.journal.on_commit_seconds = self.metrics.histogram(
            "sched_journal_fsync_seconds"
        ).observe

    # ------------------------------------------------------------------ #
    def job_dir(self, job_id: str) -> str:
        return os.path.join(self.jobs_root, job_id)

    def events_path(self, job_id: str) -> str:
        return os.path.join(self.job_dir(job_id), "events.jsonl")

    def _rebuild_ledger(self) -> WarmLedger:
        """Warm knowledge survives the scheduler's death: every done
        transition journals its ledger entry, replayed here."""
        ledger = WarmLedger()
        records, _ = Journal.replay(self.journal.path)
        for rec in records:
            entry = rec.get("warm_entry")
            if (rec.get("type") == "state" and rec.get("to") == "done"
                    and isinstance(entry, dict) and entry.get("key")):
                ledger.observe(entry["key"],
                               entry.get("compile_seconds", 0.0),
                               entry.get("peak_bytes"))
        return ledger

    def _transition(self, job_id: str, to: str, **info) -> JobRecord:
        frm = self.queue.jobs[job_id].state
        rec = self.queue.transition(job_id, to, **info)
        self._sink.event(
            "job", "state", job=job_id,
            **{"from": frm, "to": to},
            reason=info.get("reason"),
        )
        return rec

    # ------------------------------------------------------------------ #
    # Recovery: replay + re-adopt / requeue in-flight work
    # ------------------------------------------------------------------ #
    def recover(self) -> dict:
        if self._recovered:
            return {}
        self._recovered = True
        adopted = requeued = completed = 0
        for rec in list(self.queue.in_flight()):
            job_id = rec.job_id
            jd = self.job_dir(job_id)
            if rec.state == "admitted":
                # admitted but the running record never landed: any
                # spawned worker died with the daemon (pdeathsig)
                self._transition(job_id, "queued",
                                 reason="recovered-unstarted")
                requeued += 1
                continue
            if rec.pid and _pid_runs_job(rec.pid, jd):
                self._handles[job_id] = {
                    "handle": AdoptedHandle(rec.pid, jd),
                    "started": time.monotonic(),
                    "mesh_arg": None,
                    "adopted": True,
                }
                self._sink.event("sched", "adopt", job=job_id,
                                 pid=rec.pid)
                adopted += 1
                continue
            rc = _artifact_rc(jd)
            if rc == 0:
                self._finalize_done(rec, rc, mesh_arg=None,
                                    recovered=True)
                completed += 1
            elif rc == EXIT_PREEMPTED:
                self._transition(job_id, "preempted", rc=rc,
                                 reason="recovered-preempted")
                self._transition(job_id, "queued",
                                 reason="requeue-after-preemption")
                requeued += 1
            else:
                self._transition(job_id, "queued",
                                 reason="recovered-dead",
                                 dt_scale=rec.dt_scale)
                requeued += 1
        report = {
            "records": self.replay_report.get("records", 0),
            "torn_lines": self.replay_report.get("torn_lines", 0),
            "problems": len(self.replay_report.get("problems", [])),
            "jobs": len(self.queue.jobs),
            "adopted": adopted,
            "requeued": requeued,
            "completed": completed,
        }
        self._sink.event("sched", "recover", **report)
        return report

    # ------------------------------------------------------------------ #
    # Submission
    # ------------------------------------------------------------------ #
    def submit(self, spec: JobSpec) -> JobRecord:
        rec = self.queue.submit(spec)
        self._sink.event(
            "job", "submit", job=spec.job_id,
            priority=spec.priority, devices=spec.devices,
            max_retries=spec.max_retries,
        )
        self.metrics.counter("sched_jobs_submitted_total").inc()
        if self.journal.degraded:
            self._sink.event("sched", "journal_degraded",
                             pending=len(self.journal._pending))
        return rec

    def _ingest_spool(self) -> None:
        def on_skip(name, reason):
            self._sink.event("sched", "spool_skip",
                             file=name, error=reason)

        for rec in ingest_spool(self.root, self.queue, on_skip=on_skip):
            self._sink.event(
                "job", "submit", job=rec.job_id,
                priority=rec.spec.priority, devices=rec.spec.devices,
                max_retries=rec.spec.max_retries,
            )
            self.metrics.counter("sched_jobs_submitted_total").inc()

    # ------------------------------------------------------------------ #
    # Attempt lifecycle
    # ------------------------------------------------------------------ #
    def _reserved_devices(self) -> int:
        return sum(r.granted_devices for r in self.queue.in_flight())

    def _build_argv(self, rec: JobRecord,
                    mesh_arg: Optional[str]) -> List[str]:
        from multigpu_advectiondiffusion_tpu.resilience.recovery import (
            find_latest_checkpoint,
        )

        spec = rec.spec
        jd = self.job_dir(rec.job_id)
        argv = list(spec.argv)
        total = _flag_value(argv, "--iters")
        latest = find_latest_checkpoint(jd, report=lambda m: None)
        if latest is not None and total is not None:
            done_it = _ckpt_iteration(latest)
            if done_it is not None:
                remaining = max(0, int(total) - done_it)
                argv = _set_flag(argv, "--iters", str(remaining))
        argv += ["--resume", "auto", "--save", jd,
                 "--metrics", self.events_path(rec.job_id)]
        if self.aot_dir:
            argv += ["--aot-cache", self.aot_dir]
        if rec.dt_scale != 1.0:
            argv += ["--dt-scale", f"{rec.dt_scale:.12g}"]
        if mesh_arg:
            argv += ["--mesh", mesh_arg]
        return argv

    def _start(self, rec: JobRecord, info: dict) -> None:
        job_id = rec.job_id
        jd = self.job_dir(job_id)
        os.makedirs(jd, exist_ok=True)
        # stale terminal markers from the previous attempt would
        # misclassify this one (adoption reads artifacts)
        for name in ("summary.json", "preempt.json", "result.bin",
                     "crash.json"):
            try:
                os.remove(os.path.join(jd, name))
            except FileNotFoundError:
                pass
        mesh_arg = self.admission.mesh_arg(
            rec.spec, info.get("granted_devices", 1)
        )
        argv = self._build_argv(rec, mesh_arg)
        attempt = rec.attempts + 1
        handle = self.runner.start(
            argv, rec.spec.env, os.path.join(jd, "job.log")
        )
        self._transition(
            job_id, "running", pid=getattr(handle, "pid", None),
            attempt=attempt, dt_scale=rec.dt_scale,
        )
        self._handles[job_id] = {
            "handle": handle,
            "started": time.monotonic(),
            "mesh_arg": mesh_arg,
            "adopted": False,
        }
        self._sink.event(
            "job", "start", job=job_id,
            pid=getattr(handle, "pid", None), attempt=attempt,
            mesh=mesh_arg, dt_scale=rec.dt_scale,
            warm=bool(info.get("warm")),
        )

    def _admit(self) -> int:
        admitted = 0
        for rec in self.queue.runnable():
            free_slots = self.max_concurrent - len(self._handles)
            free_devices = (
                self.admission.device_budget - self._reserved_devices()
            )
            streams = [self.events_path(j) for j in self._handles]
            verdict, info = self.admission.decide(
                rec, free_slots, free_devices, streams
            )
            if verdict != "admit":
                reason = info.get("reason", "?")
                if self._deferred.get(rec.job_id) != reason:
                    self._deferred[rec.job_id] = reason
                    self._sink.event("sched", "defer", job=rec.job_id,
                                     reason=reason, **{
                                         k: v for k, v in info.items()
                                         if k != "reason"
                                     })
                # strict priority: never backfill past a deferred
                # higher-priority job
                break
            self._deferred.pop(rec.job_id, None)
            self._transition(
                rec.job_id, "admitted",
                granted_devices=info["granted_devices"],
                warm=info["warm"], warm_key=info["warm_key"],
            )
            self._sink.event(
                "sched", "admit", job=rec.job_id,
                granted_devices=info["granted_devices"],
                warm=info["warm"],
                expected_compile_seconds_saved=info.get(
                    "expected_compile_seconds_saved"),
                mem_in_use=info.get("mem_in_use"),
                free_devices=free_devices,
            )
            self.metrics.counter("sched_jobs_admitted_total").inc()
            self._start(rec, info)
            admitted += 1
        return admitted

    def _observe_checkpoints(self) -> None:
        from multigpu_advectiondiffusion_tpu.resilience.recovery import (
            scan_checkpoints,
        )

        for job_id in list(self._handles):
            rec = self.queue.jobs[job_id]
            if rec.state != "running":
                continue
            names = scan_checkpoints(self.job_dir(job_id))
            if names:
                self._transition(job_id, "checkpointed",
                                 checkpoint=names[0])

    def _finalize_done(self, rec: JobRecord, rc: int,
                       mesh_arg: Optional[str],
                       recovered: bool = False) -> None:
        jd = self.job_dir(rec.job_id)
        compile_s, peak = 0.0, None
        try:
            with open(os.path.join(jd, "summary.json")) as f:
                summary = json.load(f)
            compile_s = float(summary.get("compile_seconds") or 0.0)
            peak = (summary.get("memory") or {}).get("peak_bytes")
        except (OSError, ValueError, TypeError):
            summary = None
        key = warm_key(rec.spec.argv, mesh_arg)
        entry = self.admission.ledger.observe(key, compile_s, peak)
        self._transition(
            rec.job_id, "done", rc=rc, recovered=recovered,
            warm_entry={"key": key, **entry},
        )

    def _finalize_failure(self, rec: JobRecord, rc: int) -> None:
        jd = self.job_dir(rec.job_id)
        policy, reason = classify_failure(rc, jd)
        entry = {
            "attempt": rec.attempts, "rc": rc, "policy": policy,
            "reason": reason, "wall": round(time.time(), 3),
        }
        prior = sum(1 for f in rec.failures
                    if f.get("policy") == policy)
        budget = RETRY_POLICIES[policy]["budget"]
        if budget is None:
            budget = rec.spec.max_retries
        if prior < budget:
            dt_scale = rec.dt_scale
            if RETRY_POLICIES[policy]["dt_backoff"]:
                backoff = _flag_value(rec.spec.argv, "--dt-backoff")
                dt_scale *= float(backoff) if backoff else 0.5
            self._transition(
                rec.job_id, "queued", failure=entry,
                dt_scale=dt_scale, reason=f"retry-{policy}",
            )
            self._sink.event(
                "sched", "retry", job=rec.job_id,
                attempt=rec.attempts, policy=policy,
                dt_scale=dt_scale, reason=reason,
            )
            self.metrics.counter("sched_retries_total").inc()
            return
        # retries exhausted for this policy: terminal, with forensics
        self._transition(rec.job_id, "failed", failure=entry,
                         reason=policy)
        from multigpu_advectiondiffusion_tpu.utils.io import (
            atomic_write_text,
        )

        log_tail = ""
        try:
            with open(os.path.join(jd, "job.log")) as f:
                log_tail = f.read()[-2000:]
        except OSError:
            pass
        atomic_write_text(
            os.path.join(jd, "failure.json"),
            json.dumps({
                "job": rec.job_id,
                "attempts": rec.attempts,
                "last_rc": rc,
                "policy": policy,
                "reason": reason,
                "ledger": rec.failures,
                "log_tail": log_tail,
            }, indent=1),
        )

    def _reap(self) -> int:
        reaped = 0
        for job_id in list(self._handles):
            h = self._handles[job_id]
            rc = h["handle"].poll()
            if rc is None:
                continue
            h["handle"].close()
            del self._handles[job_id]
            reaped += 1
            rec = self.queue.jobs[job_id]
            seconds = round(time.monotonic() - h["started"], 3)
            self._sink.event("job", "exit", job=job_id, rc=rc,
                             seconds=seconds,
                             adopted=bool(h.get("adopted")))
            self.metrics.counter("sched_job_exits_total").inc()
            self.metrics.histogram("sched_job_seconds").observe(seconds)
            if rc == 0:
                self._finalize_done(rec, rc, mesh_arg=h["mesh_arg"])
            elif rc == EXIT_PREEMPTED:
                self._transition(job_id, "preempted", rc=rc)
                self._transition(job_id, "queued",
                                 reason="requeue-after-preemption",
                                 dt_scale=rec.dt_scale)
            else:
                self._finalize_failure(rec, rc)
        return reaped

    def _maybe_preempt(self) -> None:
        runnable = self.queue.runnable()
        if not runnable:
            return
        top = runnable[0]
        blocked = None
        if len(self._handles) >= self.max_concurrent:
            blocked = "slots"
        elif (self.admission.device_budget
              - self._reserved_devices()) < 1:
            blocked = "devices"
        if blocked is None:
            return
        victims = sorted(
            (r for r in self.queue.in_flight()
             if r.state in ("running", "checkpointed")
             and r.spec.priority < top.spec.priority
             and not r.preempt_requested
             and r.job_id in self._handles),
            key=lambda r: (r.spec.priority, -r.order),
        )
        if not victims:
            return
        victim = victims[0]
        victim.preempt_requested = True
        self._handles[victim.job_id]["handle"].terminate()
        self._sink.event(
            "sched", "preempt", victim=victim.job_id,
            for_job=top.job_id, blocked=blocked,
            victim_priority=victim.spec.priority,
            priority=top.spec.priority,
        )
        self.metrics.counter("sched_preemptions_total").inc()

    # ------------------------------------------------------------------ #
    # The loop
    # ------------------------------------------------------------------ #
    def tick(self) -> dict:
        """One scheduler pass: ingest, observe, reap, preempt, admit."""
        self.recover()
        self._ingest_spool()
        self._observe_checkpoints()
        reaped = self._reap()
        self._maybe_preempt()
        admitted = self._admit()
        if self.journal.degraded:
            self._sink.event("sched", "journal_degraded",
                             pending=len(self.journal._pending))
        if self.lease is not None:
            self.lease.heartbeat()
        self.metrics.gauge("sched_jobs_running").set(len(self._handles))
        self.metrics.gauge("sched_jobs_open").set(
            len(self.queue.open_jobs())
        )
        self.export_metrics(force=False)
        return {
            "running": len(self._handles),
            "open": len(self.queue.open_jobs()),
            "reaped": reaped,
            "admitted": admitted,
        }

    def export_metrics(self, force: bool = True) -> Optional[dict]:
        """Publish this incarnation's atomic metrics snapshot under
        ``metrics/<proc>/`` (throttled unless forced)."""
        now = time.monotonic()
        if not force and now - self._last_export < self.metrics_every_s:
            return None
        self._last_export = now
        snap = self.metrics.write_snapshot(self.metrics_dir)
        self._sink.event(
            "metrics", "snapshot", dir=self.metrics_dir,
            counters=len(snap["counters"]),
            gauges=len(snap["gauges"]),
            histograms=len(snap["histograms"]),
        )
        return snap

    def serve(self, until_idle: bool = False,
              max_seconds: Optional[float] = None) -> dict:
        """The daemon loop. ``until_idle`` returns once every job is
        terminal (or nothing further can be admitted); otherwise serve
        runs until SIGTERM/SIGINT — which also politely drains running
        jobs through their preemption path before returning."""
        from multigpu_advectiondiffusion_tpu.resilience.preemption import (
            PreemptionGuard,
        )

        self.recover()
        self._sink.event(
            "sched", "start", root=self.root,
            max_concurrent=self.max_concurrent,
            device_budget=self.admission.device_budget,
            until_idle=bool(until_idle),
        )
        t0 = time.monotonic()
        stop_reason = "idle"
        with PreemptionGuard() as guard:
            while True:
                status = self.tick()
                if guard.should_stop:
                    stop_reason = f"signal {guard.signum}"
                    self._drain()
                    # the workers parked through their preemption path:
                    # a successor starts with zero surprise recovery
                    self.journal.append("note", note="shutdown",
                                        clean=True, pid=os.getpid(),
                                        reason=stop_reason)
                    break
                if max_seconds and time.monotonic() - t0 > max_seconds:
                    stop_reason = "max_seconds"
                    break
                if until_idle and not self._handles:
                    if not status["open"]:
                        break
                    if not status["admitted"] and not status["reaped"]:
                        stop_reason = "stalled"
                        break
                if not self._handles and not until_idle:
                    time.sleep(self.poll_seconds)
                elif self._handles:
                    time.sleep(self.poll_seconds)
        states = {}
        for r in self.queue.jobs.values():
            states[r.state] = states.get(r.state, 0) + 1
        self._sink.event("sched", "stop", reason=stop_reason,
                         states=states)
        return {"reason": stop_reason, "states": states}

    def _drain(self, timeout: float = 30.0) -> None:
        """Graceful shutdown: SIGTERM every worker (they checkpoint and
        exit 75 -> requeued), reap what lands before the timeout."""
        for h in self._handles.values():
            h["handle"].terminate()
        deadline = time.monotonic() + timeout
        while self._handles and time.monotonic() < deadline:
            self._reap()
            if self._handles:
                time.sleep(0.1)

    def close(self) -> None:
        self.export_metrics(force=True)
        self.journal.close()
        if self.lease is not None:
            self._sink.event("lease", "release", pid=os.getpid())
            self.lease.release()
            self.lease = None
        self._sink.close()
