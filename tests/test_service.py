"""Crash-safe multi-run scheduler (ISSUE 14): tier-1 coverage of the
journal, the queue state machine, admission control, retry policies,
per-job namespacing and the disk-full degradation — everything that
does not need a real SIGKILL (the chaos half lives in test_chaos.py).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from multigpu_advectiondiffusion_tpu.cli.__main__ import main as cli_main
from multigpu_advectiondiffusion_tpu.resilience import faults
from multigpu_advectiondiffusion_tpu.resilience.recovery import (
    find_latest_checkpoint,
)
from multigpu_advectiondiffusion_tpu.service import (
    AdmissionController,
    InProcessRunner,
    Journal,
    JobQueue,
    JobSpec,
    Scheduler,
    WarmLedger,
    classify_failure,
    ingest_spool,
    submit_to_spool,
    verify_records,
)
from multigpu_advectiondiffusion_tpu.service.daemon import (
    FinishedHandle,
    _artifact_rc,
    _flag_value,
)
from multigpu_advectiondiffusion_tpu.telemetry import schema


@pytest.fixture(autouse=True)
def _isolate_aot_cache():
    """In-process workers configure the process-wide AOT cache via
    --aot-cache; restore the knobs so one test's cache directory can
    never leak into another test's dispatches."""
    from multigpu_advectiondiffusion_tpu.tuning import aot_cache

    saved = dict(aot_cache._state)
    yield
    aot_cache._state.clear()
    aot_cache._state.update(saved)


def _events(path):
    return [json.loads(line) for line in open(path) if line.strip()]


def _dead_pid() -> int:
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    return proc.pid


_TINY = ["diffusion2d", "--n", "16", "12", "--iters", "6",
         "--checkpoint-every", "3"]


# --------------------------------------------------------------------- #
# Journal: commit records, torn tails, ENOSPC degradation
# --------------------------------------------------------------------- #
def test_journal_roundtrip_and_seq_continuation(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with Journal(path) as j:
        j.append("submit", job="a", spec={"x": 1})
        j.append("state", job="a", **{"from": "queued", "to": "admitted"})
    with Journal(path) as j:
        rec = j.append("note", msg="reopened")
    assert rec["seq"] == 3  # sequence continues across incarnations
    records, torn = Journal.replay(path)
    assert torn == 0
    assert [r["seq"] for r in records] == [1, 2, 3]
    assert records[0]["spec"] == {"x": 1}


def test_journal_replay_skips_torn_and_corrupt_lines(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with Journal(path) as j:
        j.append("submit", job="a", spec={})
        j.append("submit", job="b", spec={})
    text = open(path).read().splitlines()
    # text[0] is the schema header (ISSUE 20); corrupt the first
    # PAYLOAD record: a bit-flipped CRC mid-file plus a torn tail
    flipped = text[1].replace('"crc": "', '"crc": "0')[:len(text[1])]
    with open(path, "w") as f:
        f.write(text[0] + "\n" + flipped + "\n" + text[2] + "\n"
                + '{"seq": 3, "ty')
    records, torn = Journal.replay(path)
    assert torn == 2
    assert [r["job"] for r in records] == ["b"]


def test_journal_enospc_degrades_then_heals_in_order(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = Journal(path)
    with faults.disk_full(targets=("journal",), times=2) as fired:
        rec = j.append("submit", job="a", spec={})
        assert fired["count"] == 2  # first write + its retry
    assert j.degraded and rec["durable"] is False
    # the next append drains the parked record first — order holds
    rec2 = j.append("submit", job="b", spec={})
    assert rec2["durable"] is True and not j.degraded
    j.close()
    records, torn = Journal.replay(path)
    assert torn == 0
    assert [(r["seq"], r["job"]) for r in records] == [(1, "a"), (2, "b")]


# --------------------------------------------------------------------- #
# Queue: transitions, replay, verification, spool
# --------------------------------------------------------------------- #
def test_transition_table_enforced_and_replayed(tmp_path):
    q = JobQueue(Journal(str(tmp_path / "j.jsonl")))
    q.submit(JobSpec(job_id="a", argv=list(_TINY)))
    with pytest.raises(ValueError, match="illegal"):
        q.transition("a", "running")  # queued -> running skips admitted
    q.transition("a", "admitted", granted_devices=2)
    q.transition("a", "running", pid=42, attempt=1)
    q.transition("a", "checkpointed")
    q.transition("a", "preempted")
    q.transition("a", "queued", dt_scale=0.5,
                 failure={"attempt": 1, "policy": "diverged"})
    q2, report = JobQueue.replay(Journal(q.journal.path, fsync=False))
    rec = q2.jobs["a"]
    assert rec.state == "queued"
    assert rec.attempts == 1
    assert rec.dt_scale == 0.5
    assert rec.granted_devices == 0  # freed with the requeue
    assert [f["policy"] for f in rec.failures] == ["diverged"]
    assert report["problems"] == []


def test_verify_records_catches_illegal_and_incomplete(tmp_path):
    j = Journal(str(tmp_path / "j.jsonl"))
    q = JobQueue(j)
    q.submit(JobSpec(job_id="a", argv=list(_TINY)))
    q.transition("a", "admitted")
    records, torn = Journal.replay(j.path)
    assert verify_records(records, torn) == []
    # incomplete: --require-complete style check trips
    problems = verify_records(records, torn, require_complete=True)
    assert any("terminal" in p for p in problems)
    # a hand-forged illegal transition record trips the linearizer
    j.append("state", job="a", **{"from": "queued", "to": "done"})
    records, torn = Journal.replay(j.path)
    problems = verify_records(records, torn)
    assert any("illegal" in p or "journal has it" in p
               for p in problems)


def test_spec_rejects_scheduler_owned_flags():
    for flag in ("--save", "--metrics", "--resume", "--mesh",
                 "--aot-cache", "--coordinator", "--dt-scale"):
        with pytest.raises(ValueError, match="scheduler-owned"):
            JobSpec(job_id="x",
                    argv=["diffusion2d", flag, "v"]).validate()


def test_spool_submit_ingest_and_dedupe(tmp_path):
    root = str(tmp_path)
    submit_to_spool(root, JobSpec(job_id="a", argv=list(_TINY)))
    with pytest.raises(ValueError, match="already spooled"):
        submit_to_spool(root, JobSpec(job_id="a", argv=list(_TINY)))
    submit_to_spool(root, JobSpec(job_id="b", argv=list(_TINY),
                                  priority=3))
    q = JobQueue(Journal(os.path.join(root, "journal.jsonl")))
    got = ingest_spool(root, q)
    assert sorted(r.job_id for r in got) == ["a", "b"]
    assert os.listdir(os.path.join(root, "spool")) == []
    # daemon died between journaling and unlinking: the re-spooled
    # duplicate is dropped, not resubmitted
    submit_to_spool(root, JobSpec(job_id="a", argv=list(_TINY)))
    assert ingest_spool(root, q) == []
    assert q.jobs["b"].spec.priority == 3
    assert [r.job_id for r in q.runnable()] == ["b", "a"]  # priority


# --------------------------------------------------------------------- #
# Admission: elastic device grants, memory watermarks, warm ledger
# --------------------------------------------------------------------- #
def test_grant_devices_largest_fitting_divisor():
    a = AdmissionController(device_budget=8)
    assert a.grant_devices(4, 8) == 4
    assert a.grant_devices(4, 3) == 2   # divisor rule, not 3
    assert a.grant_devices(4, 1) == 1
    assert a.grant_devices(4, 0) == 0
    assert a.grant_devices(0, 5) == 1   # unsharded request
    assert a.grant_devices(6, 4) == 3


def test_memory_watermark_defers_until_budget_frees(tmp_path):
    stream = str(tmp_path / "events.jsonl")
    with open(stream, "w") as f:
        f.write(json.dumps({"t": 0.1, "proc": 0, "kind": "mem",
                            "name": "watermark", "bytes_in_use": 100,
                            "peak_bytes": 700, "source": "x"}) + "\n")
        f.write(json.dumps({"t": 0.2, "proc": 0, "kind": "mem",
                            "name": "watermark", "bytes_in_use": 100,
                            "peak_bytes": 800, "source": "x"}) + "\n")
    ledger = WarmLedger()
    spec = JobSpec(job_id="x", argv=list(_TINY))
    from multigpu_advectiondiffusion_tpu.service.admission import (
        latest_watermark,
        warm_key,
    )

    assert latest_watermark(stream) == 800  # the newest peak wins
    ledger.observe(warm_key(spec.argv, None), 1.5, peak_bytes=300)
    ctl = AdmissionController(device_budget=1, mem_budget_bytes=1000,
                              ledger=ledger)
    rec = type("R", (), {"spec": spec})()
    verdict, info = ctl.decide(rec, 1, 1, [stream])
    assert verdict == "defer" and info["reason"] == "memory"
    assert info["mem_in_use"] == 800 and info["mem_estimate"] == 300
    verdict, info = ctl.decide(rec, 1, 1, [])  # the heavy job finished
    assert verdict == "admit"
    assert info["warm"] is True
    assert info["expected_compile_seconds_saved"] == 1.5


# --------------------------------------------------------------------- #
# Retry policies (scripted runner): classification, dt inheritance,
# bounded budgets, the failure ledger
# --------------------------------------------------------------------- #
class ScriptedRunner:
    """Deterministic outcomes per job id; each script step is an rc or
    a callable(job_dir) -> rc that can plant crash evidence first."""

    def __init__(self, script):
        self.script = {k: list(v) for k, v in script.items()}
        self.started = {k: [] for k in script}

    def start(self, argv, env, log_path):
        del env, log_path
        job_dir = _flag_value(argv, "--save")
        job_id = os.path.basename(job_dir)
        self.started[job_id].append(list(argv))
        os.makedirs(job_dir, exist_ok=True)
        step = self.script[job_id].pop(0)
        rc = step(job_dir) if callable(step) else step
        return FinishedHandle(rc)


def _crash(job_dir, etype, message, errno=None):
    with open(os.path.join(job_dir, "crash.json"), "w") as f:
        json.dump({"type": etype, "message": message, "errno": errno}, f)
    return 1


def _drive(sched, max_ticks=50):
    for _ in range(max_ticks):
        sched.tick()
        if not sched.queue.open_jobs():
            return
    raise AssertionError(
        f"queue never drained: "
        f"{[(r.job_id, r.state) for r in sched.queue.jobs.values()]}"
    )


def test_diverged_retries_inherit_dt_backoff(tmp_path):
    runner = ScriptedRunner({
        "a": [
            lambda d: _crash(d, "SolverDivergedError",
                             "diverged at step 7"),
            lambda d: _crash(d, "SolverDivergedError",
                             "diverged at step 9"),
            0,
        ],
    })
    sched = Scheduler(str(tmp_path / "root"), runner=runner,
                      aot_cache=False, fsync=False)
    sched.submit(JobSpec(job_id="a", argv=list(_TINY), max_retries=2))
    _drive(sched)
    rec = sched.queue.jobs["a"]
    assert rec.state == "done" and rec.attempts == 3
    assert [f["policy"] for f in rec.failures] == ["diverged"] * 2
    # dt-backoff inheritance: attempt 2 starts at 0.5, attempt 3 at 0.25
    argvs = runner.started["a"]
    assert _flag_value(argvs[0], "--dt-scale") is None
    assert float(_flag_value(argvs[1], "--dt-scale")) == 0.5
    assert float(_flag_value(argvs[2], "--dt-scale")) == 0.25
    # ...and the inherited scale is journal-replayable
    q2, _ = JobQueue.replay(Journal(sched.journal.path, fsync=False))
    assert q2.jobs["a"].dt_scale == 0.25
    sched.close()


def test_retry_budget_exhaustion_writes_forensics(tmp_path):
    runner = ScriptedRunner({
        "a": [lambda d: _crash(d, "SolverDivergedError", "boom")] * 3,
        "b": [0],
    })
    sched = Scheduler(str(tmp_path / "root"), runner=runner,
                      aot_cache=False, fsync=False)
    sched.submit(JobSpec(job_id="a", argv=list(_TINY), max_retries=2))
    sched.submit(JobSpec(job_id="b", argv=list(_TINY)))
    _drive(sched)
    assert sched.queue.jobs["a"].state == "failed"
    assert sched.queue.jobs["b"].state == "done"  # the daemon survived
    forensics = json.loads(
        open(os.path.join(sched.job_dir("a"), "failure.json")).read()
    )
    assert forensics["policy"] == "diverged"
    assert forensics["attempts"] == 3
    # one ledger entry per failed attempt, terminal one included
    assert len(forensics["ledger"]) == 3
    sched.close()


def test_distinct_policies_classified(tmp_path):
    jd = str(tmp_path / "jd")
    os.makedirs(jd)
    assert classify_failure(76, jd)[0] == "rank_failure"
    assert classify_failure(77, jd)[0] == "sdc"
    assert classify_failure(1, jd)[0] == "error"
    _crash(jd, "SDCDetectedError", "duplicate executions differ")
    assert classify_failure(1, jd)[0] == "sdc"
    _crash(jd, "OSError", "No space left on device (injected)",
           errno=28)
    assert classify_failure(1, jd)[0] == "disk_full"
    _crash(jd, "PhysicsViolationError", "tv growth")
    assert classify_failure(1, jd)[0] == "diverged"


def test_preempted_exit_requeues_without_burning_retries(tmp_path):
    runner = ScriptedRunner({"a": [75, 75, 0]})
    sched = Scheduler(str(tmp_path / "root"), runner=runner,
                      aot_cache=False, fsync=False)
    sched.submit(JobSpec(job_id="a", argv=list(_TINY), max_retries=0))
    _drive(sched)
    rec = sched.queue.jobs["a"]
    # max_retries=0, yet two preemptions did not fail the job — 75 is
    # a requeue, not a failure
    assert rec.state == "done" and rec.attempts == 3
    assert rec.failures == []
    evs = _events(os.path.join(sched.root, "sched_events.jsonl"))
    chain = [(e["from"], e["to"]) for e in evs
             if e["kind"] == "job" and e["name"] == "state"
             and e["job"] == "a"]
    assert chain.count(("running", "preempted")) == 2
    assert chain.count(("preempted", "queued")) == 2
    sched.close()


# --------------------------------------------------------------------- #
# Disk-full degradation (real checkpoint path, in-process worker)
# --------------------------------------------------------------------- #
def test_disk_full_checkpoint_retries_once_then_fails(tmp_path):
    sched = Scheduler(str(tmp_path / "root"),
                      runner=InProcessRunner(), aot_cache=False,
                      fsync=False)
    sched.submit(JobSpec(job_id="nospace", argv=list(_TINY),
                         max_retries=5))
    sched.submit(JobSpec(job_id="fine", argv=list(_TINY)))
    with faults.disk_full(targets=("checkpoint",)):
        for _ in range(20):
            sched.tick()
            if sched.queue.jobs["nospace"].state == "failed":
                break
    _drive(sched)  # the healthy job still completes
    rec = sched.queue.jobs["nospace"]
    # the disk_full policy is bounded at ONE retry regardless of the
    # job's own (generous) max_retries
    assert rec.state == "failed" and rec.attempts == 2
    assert [f["policy"] for f in rec.failures] == ["disk_full"] * 2
    forensics = json.loads(
        open(os.path.join(sched.job_dir("nospace"),
                          "failure.json")).read()
    )
    assert "No space left" in forensics["reason"]
    assert sched.queue.jobs["fine"].state == "done"
    sched.close()


# --------------------------------------------------------------------- #
# Per-job namespacing (satellite): no cross-job checkpoint adoption
# --------------------------------------------------------------------- #
def test_job_namespaces_never_collide(tmp_path):
    sched = Scheduler(str(tmp_path / "root"),
                      runner=InProcessRunner(), aot_cache=False,
                      fsync=False)
    # identical configs, same save ROOT — the classic collision setup
    sched.submit(JobSpec(job_id="a", argv=list(_TINY)))
    sched.submit(JobSpec(job_id="b", argv=list(_TINY)))
    _drive(sched)
    dir_a, dir_b = sched.job_dir("a"), sched.job_dir("b")
    picked_a = find_latest_checkpoint(dir_a)
    picked_b = find_latest_checkpoint(dir_b)
    assert picked_a and picked_a.startswith(dir_a)
    assert picked_b and picked_b.startswith(dir_b)
    assert os.path.dirname(picked_a) != os.path.dirname(picked_b)
    # the resume argv a retry would use scans ONLY the job's own dir
    argv = sched._build_argv(sched.queue.jobs["a"], None)
    assert _flag_value(argv, "--save") == dir_a
    assert dir_b not in " ".join(argv)
    # telemetry sinks are namespaced too: each stream carries exactly
    # its own run, no interleaving
    for jid in ("a", "b"):
        evs = _events(sched.events_path(jid))
        runs = [e for e in evs if e["kind"] == "span"
                and e["name"] == "run_solver"
                and e.get("phase") == "begin"]
        assert len(runs) == 1
    sched.close()


# --------------------------------------------------------------------- #
# Recovery: replay + adopt/classify/requeue (no real SIGKILL here)
# --------------------------------------------------------------------- #
def _plant_journal(root, state_chain, pid=None, job_id="a"):
    j = Journal(os.path.join(root, "journal.jsonl"))
    q = JobQueue(j)
    q.submit(JobSpec(job_id=job_id, argv=list(_TINY)))
    for to in state_chain:
        info = {}
        if to == "running":
            info = {"pid": pid, "attempt": 1}
        elif to == "admitted":
            info = {"granted_devices": 1}
        q.transition(job_id, to, **info)
    j.close()


def test_recover_requeues_dead_inflight_job(tmp_path):
    root = str(tmp_path / "root")
    _plant_journal(root, ["admitted", "running"], pid=_dead_pid())
    runner = ScriptedRunner({"a": [0]})
    sched = Scheduler(root, runner=runner, aot_cache=False, fsync=False)
    rep = sched.recover()
    assert rep["requeued"] == 1 and rep["adopted"] == 0
    assert sched.queue.jobs["a"].state == "queued"
    _drive(sched)
    assert sched.queue.jobs["a"].state == "done"
    # the resume argv carries --resume auto for the recovered attempt
    assert _flag_value(runner.started["a"][0], "--resume") == "auto"
    sched.close()


def test_recover_classifies_finished_orphan_by_artifacts(tmp_path):
    root = str(tmp_path / "root")
    _plant_journal(root, ["admitted", "running"], pid=_dead_pid())
    jd = os.path.join(root, "jobs", "a")
    os.makedirs(jd)
    with open(os.path.join(jd, "summary.json"), "w") as f:
        json.dump({"compile_seconds": 0.2}, f)
    sched = Scheduler(root, runner=ScriptedRunner({"a": []}),
                      aot_cache=False, fsync=False)
    rep = sched.recover()
    assert rep["completed"] == 1
    assert sched.queue.jobs["a"].state == "done"
    sched.close()


def test_recover_requeues_preempted_orphan(tmp_path):
    root = str(tmp_path / "root")
    _plant_journal(root, ["admitted", "running", "checkpointed"],
                   pid=_dead_pid())
    jd = os.path.join(root, "jobs", "a")
    os.makedirs(jd)
    with open(os.path.join(jd, "preempt.json"), "w") as f:
        json.dump({"iteration": 3}, f)
    sched = Scheduler(root, runner=ScriptedRunner({"a": []}),
                      aot_cache=False, fsync=False)
    sched.recover()
    assert sched.queue.jobs["a"].state == "queued"
    records, _ = Journal.replay(sched.journal.path)
    chain = [(r.get("from"), r.get("to")) for r in records
             if r.get("type") == "state"]
    assert ("checkpointed", "preempted") in chain
    assert ("preempted", "queued") in chain
    sched.close()


def test_recover_pid_reuse_guard_blocks_false_adoption(tmp_path):
    # our own (alive) pid, but its cmdline does not mention the job
    # dir: adoption must refuse and requeue instead
    root = str(tmp_path / "root")
    _plant_journal(root, ["admitted", "running"], pid=os.getpid())
    sched = Scheduler(root, runner=ScriptedRunner({"a": [0]}),
                      aot_cache=False, fsync=False)
    rep = sched.recover()
    assert rep["adopted"] == 0 and rep["requeued"] == 1
    sched.close()


def test_artifact_classifier(tmp_path):
    jd = str(tmp_path)
    assert _artifact_rc(jd) == 1
    open(os.path.join(jd, "preempt.json"), "w").write("{}")
    assert _artifact_rc(jd) == 75
    open(os.path.join(jd, "summary.json"), "w").write("{}")
    assert _artifact_rc(jd) == 0


# --------------------------------------------------------------------- #
# Warm admission end to end (in-process): the second identical job
# admits warm and serves every dispatch from the AOT cache
# --------------------------------------------------------------------- #
def test_warm_admission_second_identical_job_hits_aot(tmp_path):
    sched = Scheduler(str(tmp_path / "root"),
                      runner=InProcessRunner(), fsync=False)
    sched.submit(JobSpec(job_id="cold", argv=list(_TINY)))
    sched.submit(JobSpec(job_id="warm", argv=list(_TINY)))
    _drive(sched)
    evs = _events(os.path.join(sched.root, "sched_events.jsonl"))
    admits = {e["job"]: e for e in evs
              if e["kind"] == "sched" and e["name"] == "admit"}
    assert admits["cold"]["warm"] is False
    assert admits["warm"]["warm"] is True
    assert admits["warm"]["expected_compile_seconds_saved"] > 0
    # zero recompiles: the warm job's stream has hits and no miss/store
    warm_evs = _events(sched.events_path("warm"))
    aot = [e["name"] for e in warm_evs if e["kind"] == "aot_cache"]
    assert "hit" in aot
    assert not [n for n in aot if n in ("miss", "store")]
    sched.close()


def test_aot_dispatch_key_separates_physics_scalars():
    """Regression for the cross-job cache collision the scheduler's
    shared per-root AOT cache exposed: two jobs differing only in K
    must never share a serialized executable (dt = c*dx^2/K is a
    compiled-in constant — the K=0.7 job deserializing the K=1.0 blob
    marches the wrong clock)."""
    from multigpu_advectiondiffusion_tpu import (
        DiffusionConfig,
        DiffusionSolver,
        Grid,
    )
    from multigpu_advectiondiffusion_tpu.tuning import aot_cache

    g = Grid.make(8, 8, lengths=2.0)
    k1 = aot_cache.dispatch_key(
        DiffusionSolver(DiffusionConfig(grid=g, diffusivity=1.0)), "p"
    )
    k2 = aot_cache.dispatch_key(
        DiffusionSolver(DiffusionConfig(grid=g, diffusivity=0.7)), "p"
    )
    k1_again = aot_cache.dispatch_key(
        DiffusionSolver(DiffusionConfig(grid=g, diffusivity=1.0)), "p"
    )
    assert k1 != k2
    assert k1 == k1_again  # deterministic across instances


# --------------------------------------------------------------------- #
# serve --verify CLI + the schema/timeline satellites
# --------------------------------------------------------------------- #
def test_serve_verify_cli_passes_and_trips(tmp_path):
    root = str(tmp_path / "root")
    sched = Scheduler(root, runner=ScriptedRunner({"a": [0]}),
                      aot_cache=False, fsync=False)
    sched.submit(JobSpec(job_id="a", argv=list(_TINY)))
    _drive(sched)
    sched.close()
    assert cli_main(["serve", "--root", root, "--verify",
                     "--require-complete"]) is None
    # truncating the tail un-terminates the job: --require-complete
    # must trip (the sched_gate.sh selftest fixture)
    lines = open(os.path.join(root, "journal.jsonl")).read().splitlines()
    with open(os.path.join(root, "journal.jsonl"), "w") as f:
        f.write("\n".join(lines[:-1]) + "\n" + lines[-1][:20])
    with pytest.raises(SystemExit):
        cli_main(["serve", "--root", root, "--verify",
                  "--require-complete"])


def test_sched_events_validate_and_render_timeline(tmp_path):
    runner = ScriptedRunner({
        "a": [lambda d: _crash(d, "SolverDivergedError", "x"), 0],
    })
    sched = Scheduler(str(tmp_path / "root"), runner=runner,
                      aot_cache=False, fsync=False)
    sched.submit(JobSpec(job_id="a", argv=list(_TINY), priority=2))
    _drive(sched)
    sched.close()
    stream = os.path.join(sched.root, "sched_events.jsonl")
    for ev in _events(stream):
        assert schema.validate_event(ev) == [], ev
    from multigpu_advectiondiffusion_tpu.telemetry import analyze

    report = analyze.analyze([stream])
    jobs = report.queue["jobs"]
    assert [j["job"] for j in jobs] == ["a"]
    assert jobs[0]["attempts"] == 2
    assert jobs[0]["retries"][0]["policy"] == "diverged"
    states = [p["state"] for p in jobs[0]["states"]]
    assert states[0] == "queued" and states[-1] == "done"
    text = report.format_text()
    assert "job queue timeline" in text
    assert "retry [diverged]" in text


def test_concurrent_job_processes_refused_off_the_cpu(tmp_path,
                                                      monkeypatch):
    """Each job is its own CLI process that would claim every chip of
    the host; more than one at a time is refused unless the jobs are
    pinned to the CPU."""
    from multigpu_advectiondiffusion_tpu.service.daemon import Scheduler

    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(ValueError, match="claim every chip"):
        Scheduler(str(tmp_path / "a"), max_concurrent=2)
    Scheduler(str(tmp_path / "b"), max_concurrent=1).close()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    Scheduler(str(tmp_path / "c"), max_concurrent=2).close()
