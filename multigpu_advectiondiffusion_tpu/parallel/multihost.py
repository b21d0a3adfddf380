"""Multi-host (DCN) execution support.

The reference scales across nodes by launching MPI ranks under
``mpirun`` with host-staged point-to-point messaging
(``MultiGPU/*/main.c``, OpenMPI/MVAPICH2 — ``DiffusionMPICUDA.h:75-81``).
The TPU-native equivalent: one Python process per host calls
:func:`initialize` (``jax.distributed``), every host sees the global
device set, and a *hybrid* mesh places the outermost decomposition axis
on DCN while inner axes ride ICI. The same ``shard_map`` halo-exchange
program then runs unchanged — XLA routes each ``ppermute`` hop over ICI
or DCN by device placement.

Single-host runs never need this module; it is the opt-in scale-out
layer (SURVEY §2.4 multi-node row).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    attempts: Optional[int] = None,
    backoff_seconds: Optional[float] = None,
    timeout_seconds: Optional[float] = None,
) -> None:
    """Bring up the jax.distributed runtime (InitializeMPI analog,
    ``Tools.c:228-234``). On managed TPU pods all arguments auto-detect;
    on hand-rolled clusters pass coordinator/process info explicitly.

    Coordinator join is retried under exponential backoff: on a real
    cluster the coordinator process routinely comes up seconds after the
    workers (restart/preemption races), and a single failed dial must
    not kill a rank that a 2-second wait would have saved. Defaults —
    3 ``attempts``, ``backoff_seconds`` 2.0 doubling per retry — are
    overridable per call or via ``TPUCFD_DIST_ATTEMPTS`` /
    ``TPUCFD_DIST_BACKOFF`` / ``TPUCFD_DIST_TIMEOUT`` (the last maps to
    jax's ``initialization_timeout``). A runtime that is
    already initialized is success, not an error (idempotent under the
    supervisor's retry paths).

    On the CPU backend (the virtual-device demo/test world) JAX ships no
    default cross-process collective transport — every multiprocess
    computation fails with "not implemented" unless the gloo transport
    is selected before the runtime comes up."""
    import os
    import time

    # the CPU client's cross-process transport; the TPU client ignores
    # it. Set unconditionally: asking the backend which platform it is
    # would initialize it before jax.distributed, which must come first.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    if attempts is None:
        attempts = int(os.environ.get("TPUCFD_DIST_ATTEMPTS", "3"))
    if backoff_seconds is None:
        backoff_seconds = float(os.environ.get("TPUCFD_DIST_BACKOFF", "2.0"))
    if timeout_seconds is None:
        env = os.environ.get("TPUCFD_DIST_TIMEOUT")
        timeout_seconds = float(env) if env else None

    kwargs = dict(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    if timeout_seconds is not None:
        kwargs["initialization_timeout"] = int(timeout_seconds)

    # retry telemetry: every attempt/backoff/outcome is an ordered event
    # in the --metrics stream (the CLI installs the sink BEFORE joining
    # the distributed runtime), so a rank that spun on a dead
    # coordinator is diagnosable from its artifact instead of silent
    from multigpu_advectiondiffusion_tpu import telemetry

    attempts = max(1, attempts)
    last_exc = None
    for attempt in range(attempts):
        telemetry.event(
            "dist_init", "attempt",
            attempt=attempt + 1, attempts=attempts,
            coordinator=coordinator_address, process_id=process_id,
        )
        try:
            jax.distributed.initialize(**kwargs)
            telemetry.event("dist_init", "ok", attempt=attempt + 1)
            return
        except RuntimeError as exc:
            if "already initialized" in str(exc).lower():
                telemetry.event(
                    "dist_init", "ok", attempt=attempt + 1,
                    already_initialized=True,
                )
                return  # idempotent re-entry (supervisor retry paths)
            last_exc = exc
        except Exception as exc:  # transient dial/handshake failures
            last_exc = exc
        if attempt + 1 < attempts:
            delay = backoff_seconds * (2 ** attempt)
            telemetry.event(
                "dist_init", "retry",
                attempt=attempt + 1, backoff_seconds=delay,
                error=f"{type(last_exc).__name__}: {last_exc}"[:300],
            )
            time.sleep(delay)
    telemetry.event(
        "dist_init", "failed",
        attempts=attempts,
        error=f"{type(last_exc).__name__}: {last_exc}"[:300],
    )
    raise RuntimeError(
        f"jax.distributed.initialize failed after {attempts} attempt(s) "
        f"(coordinator={coordinator_address!r}): {last_exc}"
    ) from last_exc


def hybrid_mesh(
    ici_axes: Dict[str, int],
    dcn_axes: Dict[str, int],
) -> Mesh:
    """Mesh whose ``dcn_axes`` cross host (slice) boundaries and whose
    ``ici_axes`` stay within a slice.

    Example for 4 hosts of 8 chips solving a z-slab problem:
    ``hybrid_mesh({'dz_ici': 8}, {'dz_dcn': 4})`` then decompose z over
    ``('dz_dcn', 'dz_ici')``.
    """
    from jax.experimental import mesh_utils

    dcn_sizes = tuple(dcn_axes.values())
    ici_sizes = tuple(ici_axes.values())
    names = tuple(dcn_axes) + tuple(ici_axes)

    total = 1
    for s in dcn_sizes + ici_sizes:
        total *= s
    dcn_total = 1
    for s in dcn_sizes:
        dcn_total *= s

    devs = list(jax.devices())
    slice_ids = {getattr(d, "slice_index", None) for d in devs}
    n_slices = len(slice_ids) if None not in slice_ids else 0
    n_procs = len({d.process_index for d in devs})
    if n_slices == dcn_total:
        # Topology-aware placement: orders devices along the ICI torus so
        # ppermute halo neighbors are physically adjacent. Real
        # misconfigurations (axis sizes vs device count etc.) raise from
        # here and stay loud.
        devices = mesh_utils.create_hybrid_device_mesh(
            ici_sizes, dcn_sizes, devices=devs
        )
    elif n_procs == dcn_total:
        # The slice topology does not match the requested DCN extent
        # (e.g. multi-process CPU, where every device reports slice 0),
        # but the process count does: one process = one DCN granule —
        # the MPI-rank view of the world (Tools.c:228-242).
        devices = mesh_utils.create_hybrid_device_mesh(
            ici_sizes, dcn_sizes, devices=devs, process_is_granule=True,
        )
    elif dcn_total == 1:
        # Last-resort single-granule fallback. NOTE a single process
        # never reaches here (n_procs == 1 == dcn_total matches the
        # branch above); this covers only multi-process platforms whose
        # devices carry neither a matching slice topology nor a matching
        # process count, where a plain row-major mesh over ALL devices
        # is still a valid, if unoptimized, hybrid mesh.
        if total != len(devs):
            raise ValueError(
                f"hybrid_mesh axes need {total} devices, have {len(devs)}"
            )
        devices = np.asarray(devs).reshape(dcn_sizes + ici_sizes)
        return Mesh(devices, names)
    else:
        raise ValueError(
            f"cannot place DCN extent {dcn_total}: platform reports "
            f"{n_slices} slice(s) and {n_procs} process(es)"
        )
    # create_hybrid_device_mesh returns the devices in dcn-major order
    # (some backends flatten) — impose the dcn_sizes + ici_sizes shape
    return Mesh(np.asarray(devices).reshape(dcn_sizes + ici_sizes), names)


def process_local_devices() -> Sequence:
    """Devices attached to this process (AssignDevices analog — the
    reference binds rank -> GPU, ``Util.cu:66-74``; JAX binds
    process -> local chips automatically)."""
    return jax.local_devices()


def is_coordinator() -> bool:
    """True on process 0 (the reference's ``rank == 0`` I/O gate,
    ``main.c:82-86``)."""
    return jax.process_index() == 0


def collective_spec() -> Dict[str, tuple]:
    """Queryable collective metadata — the ``stencil_spec()``
    discipline applied to the host-collective layer: every
    ``barrier``/``agree`` tag namespace the framework issues, declared
    by its issuing module and aggregated here, plus the telemetry
    events each rendezvous emits. The static collective-schedule
    verifier (``analysis/collective_verify``) holds the extracted call
    sites to this registry in BOTH directions (an undeclared tag is
    schema drift; a declared-but-never-issued tag is a stale
    contract), and its dynamic cross-check reads the listed events
    back out of the 2-proc chaos streams. ``*`` in a tag is the
    wildcard for a runtime interpolation (the checkpoint directory)."""
    from multigpu_advectiondiffusion_tpu.parallel.halo import (
        remote_dma_spec,
    )
    from multigpu_advectiondiffusion_tpu.resilience.supervisor import (
        AGREE_TAGS,
    )
    from multigpu_advectiondiffusion_tpu.utils.io import (
        CKPTD_BARRIER_TAGS,
    )

    return {
        "barrier": tuple(CKPTD_BARRIER_TAGS),
        "agree": tuple(AGREE_TAGS),
        "events": (("sync", "barrier"), ("resilience", "agree")),
        # in-kernel remote-DMA exchange (the slab rung's dma mode):
        # the rendezvous is a Pallas make_async_remote_copy, not a
        # barrier/agree tag — declared here so the static pass proves
        # the kernel sites and this registry agree BOTH directions
        # (an undeclared remote-DMA site is schema drift; a declared
        # transport with no kernel site is a stale contract)
        "remote_dma": remote_dma_spec(),
    }


# --------------------------------------------------------------------- #
# Rank-liveness watchdog + timeout-wrapped collectives.
#
# MPI's failure model — which the reference inherits wholesale — is that
# one dead or wedged rank hangs (or aborts) the whole job: a gloo/ICI
# collective whose peer never arrives blocks forever inside C++ where
# Python cannot interrupt it. The watchdog gives every process two
# defenses:
#
# 1. per-process HEARTBEAT RECORDS in a shared directory (one JSON file
#    per rank, rewritten atomically at interval cadence) plus a monitor
#    thread that checks the peers': a record whose pid is dead (same
#    host) or whose timestamp went stale past the timeout identifies
#    the offending rank. Because the main thread may be stuck inside a
#    collective, the monitor's default response is a structured report
#    (telemetry `rank:failure` event + `rank_failure_p<K>.json`), a
#    sink flush, and `os._exit(EXIT_RANK_FAILURE)` — the survivor exits
#    with the documented code within the timeout instead of hanging;
# 2. TIMEOUT-WRAPPED COLLECTIVE ENTRY POINTS (`barrier`, `agree`,
#    `call_with_timeout`) for host-side collectives the framework
#    itself issues (checkpoint-commit barriers, rollback agreement):
#    the collective runs in a worker thread and a timeout converts an
#    indefinite wait into a RankFailureError naming the suspect rank
#    from the heartbeat records.
#
# Staleness compares the record's wall-clock stamp against the reader's
# clock — exact on one host (the test rig) and right to within NTP skew
# across hosts; the pid-liveness check (instant detection of a SIGKILLed
# peer) applies only to same-host records.
# --------------------------------------------------------------------- #

import contextlib as _contextlib  # noqa: E402
import json as _json  # noqa: E402
import os as _os  # noqa: E402
import socket as _socket  # noqa: E402
import threading as _threading  # noqa: E402
import time as _time  # noqa: E402

from multigpu_advectiondiffusion_tpu.resilience.errors import (  # noqa: E402
    EXIT_RANK_FAILURE,
    CoordinationError,
    RankFailureError,
)

_current_watchdog: Optional["RankWatchdog"] = None


def install_watchdog(watchdog: Optional["RankWatchdog"]) -> None:
    """Register ``watchdog`` as the process-wide current watchdog (the
    run driver installs it for the run's duration); ``None`` clears it.
    Timeout-wrapped collectives consult it for default timeouts and
    suspect attribution."""
    global _current_watchdog
    _current_watchdog = watchdog


def current_watchdog() -> Optional["RankWatchdog"]:
    return _current_watchdog


def _collective_timeout() -> float:
    """Default timeout for framework-issued collectives: the
    ``TPUCFD_COLLECTIVE_TIMEOUT`` env var, else 10x the installed
    watchdog's timeout (a barrier legitimately waits for the slowest
    peer's shard writes; the heartbeat monitor is the fast detector),
    else 0 (no timeout — single runs without a watchdog keep MPI
    semantics)."""
    env = _os.environ.get("TPUCFD_COLLECTIVE_TIMEOUT")
    if env:
        return float(env)
    wd = _current_watchdog
    if wd is not None and wd.timeout > 0:
        return max(10.0 * wd.timeout, 30.0)
    return 0.0


def call_with_timeout(fn, timeout_seconds: Optional[float], tag: str):
    """Run ``fn()`` (typically a host-side collective) in a worker
    thread and wait at most ``timeout_seconds``; on timeout raise a
    :class:`RankFailureError` naming the suspect rank from the current
    watchdog's heartbeat records. ``timeout_seconds`` of ``None``/0
    calls ``fn`` inline (no wrapping)."""
    if not timeout_seconds or timeout_seconds <= 0:
        return fn()
    result: dict = {}
    done = _threading.Event()

    def target():
        try:
            result["value"] = fn()
        except BaseException as exc:  # re-raised in the caller's thread
            result["error"] = exc
        finally:
            done.set()

    worker = _threading.Thread(
        target=target, daemon=True, name=f"tpucfd-collective-{tag}"
    )
    worker.start()
    if not done.wait(timeout_seconds):
        wd = _current_watchdog
        suspects = wd.suspects() if wd is not None else []
        rank = suspects[0]["rank"] if suspects else None
        raise RankFailureError(
            rank,
            f"collective {tag!r} did not complete within "
            f"{timeout_seconds:g}s",
            detected_by=jax.process_index(),
            suspects=suspects,
        )
    if "error" in result:
        raise result["error"]
    return result.get("value")


def barrier(tag: str, timeout_seconds: Optional[float] = None) -> None:
    """Cross-process barrier (``sync_global_devices``) with hang
    defense: when a watchdog is installed (or
    ``TPUCFD_COLLECTIVE_TIMEOUT`` is set) the wait is bounded and a
    timeout raises :class:`RankFailureError` instead of blocking
    forever. No-op with one process."""
    if jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    if timeout_seconds is None:
        timeout_seconds = _collective_timeout()
    call_with_timeout(
        lambda: multihost_utils.sync_global_devices(tag),
        timeout_seconds,
        f"barrier:{tag}",
    )
    # sync anchor: every rank emits this immediately after the SAME
    # barrier released — the trace analyzer's clock-alignment points
    # (telemetry/analyze.py align_clocks), alongside dist_init:ok and
    # resilience:agree
    from multigpu_advectiondiffusion_tpu import telemetry

    telemetry.event("sync", "barrier", tag=tag)


def agree(tag: str, values, timeout_seconds: Optional[float] = None):
    """Explicit cross-rank agreement: allgather ``values`` (a small
    numeric vector) from every process and assert all ranks proposed
    the same — the supervisor's rollback/checkpoint decisions call this
    so coordinated recovery is ASSERTED, never inferred. Returns the
    agreed vector. Raises :class:`CoordinationError` on a mismatch and
    :class:`RankFailureError` when a peer never shows up.

    Values ride an f32-safe lane (iteration counts compare exactly up
    to 2**24; scale factors are the same literal on every rank)."""
    arr = np.asarray(values, dtype=np.float64).ravel()
    if jax.process_count() <= 1:
        return arr
    from jax.experimental import multihost_utils

    if timeout_seconds is None:
        timeout_seconds = _collective_timeout()
    rows = call_with_timeout(
        lambda: multihost_utils.process_allgather(arr),
        timeout_seconds,
        f"agree:{tag}",
    )
    rows = np.asarray(rows).reshape(jax.process_count(), arr.size)
    if not (rows == rows[0]).all():
        raise CoordinationError(tag, rows.tolist())
    return rows[0]


def _heartbeat_path(directory: str, rank: int) -> str:
    return _os.path.join(directory, f"rank{rank}.hb.json")


def write_heartbeat(
    directory: str,
    rank: int,
    pid: Optional[int] = None,
    host: Optional[str] = None,
    wall: Optional[float] = None,
    seq: int = 0,
) -> None:
    """Atomically (tmp + rename) write one rank's heartbeat record —
    a reader never sees a torn record, only the previous one."""
    rec = {
        "rank": int(rank),
        "pid": int(pid if pid is not None else _os.getpid()),
        "host": host or _socket.gethostname(),
        "wall": float(wall if wall is not None else _time.time()),
        "seq": int(seq),
    }
    tmp = _heartbeat_path(directory, rank) + f".tmp.{_os.getpid()}"
    with open(tmp, "w") as f:
        _json.dump(rec, f)
    _os.replace(tmp, _heartbeat_path(directory, rank))


class RankWatchdog:
    """Per-process rank-liveness watchdog.

    ``start()`` writes this rank's heartbeat immediately and launches a
    daemon thread that (a) rewrites it every ``interval_seconds`` and
    (b) checks every peer's record: dead pid (same host) or a stamp
    stale past ``timeout_seconds`` triggers ``on_failure`` once with a
    :class:`RankFailureError`. The default ``on_failure`` emits a
    ``rank:failure`` telemetry event, writes a
    ``rank_failure_p<rank>.json`` forensics report into ``report_dir``,
    flushes the telemetry sink and ``os._exit(EXIT_RANK_FAILURE)`` —
    correct even when the main thread is wedged inside a collective
    (tests pass a recording callback instead).

    Records whose wall stamp predates this watchdog's start are ignored
    (minus 1 s of slack): a restarted run reusing the same heartbeat
    directory must not insta-fail on the previous incarnation's corpses.
    """

    def __init__(
        self,
        directory: str,
        timeout_seconds: float,
        interval_seconds: Optional[float] = None,
        rank: Optional[int] = None,
        num_processes: Optional[int] = None,
        on_failure=None,
        report_dir: Optional[str] = None,
    ):
        self.directory = directory
        self.timeout = float(timeout_seconds)
        self.interval = (
            float(interval_seconds)
            if interval_seconds is not None
            else max(0.1, self.timeout / 4.0)
        )
        self.rank = jax.process_index() if rank is None else int(rank)
        self.num_processes = (
            jax.process_count() if num_processes is None
            else int(num_processes)
        )
        self.report_dir = report_dir
        self._on_failure = on_failure
        self.failure: Optional[RankFailureError] = None
        self._host = _socket.gethostname()
        self._stop = _threading.Event()
        self._thread: Optional[_threading.Thread] = None
        self._seq = 0
        self._t0 = None  # monotonic start
        self._wall0 = None  # wall-clock start (record freshness floor)
        self._reported = False

    # ------------------------------------------------------------------ #
    def start(self) -> "RankWatchdog":
        _os.makedirs(self.directory, exist_ok=True)
        self._t0 = _time.monotonic()
        self._wall0 = _time.time()
        self._beat()
        self._thread = _threading.Thread(
            target=self._loop, daemon=True,
            name=f"tpucfd-watchdog-r{self.rank}",
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0 * self.interval + 1.0)
            self._thread = None

    def _beat(self) -> None:
        self._seq += 1
        try:
            write_heartbeat(self.directory, self.rank, seq=self._seq)
        except OSError:
            pass  # a transiently unwritable dir must not kill the run

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            self._beat()
            err = self.check_peers()
            if err is not None:
                self.failure = err
                self._fire(err)
                return

    # ------------------------------------------------------------------ #
    def _check_peer(self, peer: int) -> Optional[str]:
        """Reason string when ``peer`` looks dead/stalled, else None."""
        path = _heartbeat_path(self.directory, peer)
        rec = None
        try:
            with open(path) as f:
                rec = _json.load(f)
        except (OSError, ValueError):
            rec = None  # absent (or unreadable): handled below
        if rec is not None and float(rec.get("wall", 0.0)) < (
            self._wall0 - 1.0
        ):
            rec = None  # previous incarnation's record: not evidence
        if rec is None:
            if _time.monotonic() - self._t0 > self.timeout:
                return (
                    "no heartbeat record within "
                    f"{self.timeout:g}s of watchdog start"
                )
            return None
        pid = rec.get("pid")
        if rec.get("host") == self._host and pid:
            try:
                _os.kill(int(pid), 0)
            except ProcessLookupError:
                return f"process (pid {pid}) is dead"
            except (PermissionError, OSError):
                pass  # alive but not ours to signal-probe
        age = _time.time() - float(rec.get("wall", 0.0))
        if age > self.timeout:
            return (
                f"heartbeat stale for {age:.1f}s "
                f"(timeout {self.timeout:g}s)"
            )
        return None

    def check_peers(self) -> Optional[RankFailureError]:
        """One sweep over every peer; the first dead/stalled one wins."""
        for peer in range(self.num_processes):
            if peer == self.rank:
                continue
            reason = self._check_peer(peer)
            if reason is not None:
                return RankFailureError(
                    peer, reason, detected_by=self.rank,
                    suspects=self.suspects(),
                )
        return None

    def await_verdict(self, grace: Optional[float] = None):
        """Poll the peers for up to ``grace`` seconds (default: the
        timeout plus two intervals) and return the
        :class:`RankFailureError` if one emerges, else ``None``.

        Classifies an exception that RACED the monitor: a gloo
        "connection reset" often reaches the main thread within
        milliseconds of a peer's death — before its heartbeat is stale
        and while its pid may still be an unreaped zombie. Waiting one
        staleness window settles the question either way."""
        if grace is None:
            grace = self.timeout + 2.0 * self.interval
        deadline = _time.monotonic() + grace
        while True:
            err = self.failure or self.check_peers()
            if err is not None:
                return err
            if _time.monotonic() >= deadline:
                return None
            _time.sleep(min(self.interval, 0.2))

    def suspects(self) -> list:
        """Non-raising peer sweep: ``[{rank, reason}, ...]`` for every
        peer currently failing its liveness checks."""
        out = []
        for peer in range(self.num_processes):
            if peer == self.rank:
                continue
            reason = self._check_peer(peer)
            if reason is not None:
                out.append({"rank": peer, "reason": reason})
        return out

    # ------------------------------------------------------------------ #
    def report(self, err: RankFailureError) -> None:
        """Structured forensics: one ``rank:failure`` telemetry event +
        a ``rank_failure_p<rank>.json`` report in ``report_dir``, then a
        sink flush — idempotent, shared by the monitor's abort path and
        the main thread's exception path."""
        if self._reported:
            return
        self._reported = True
        from multigpu_advectiondiffusion_tpu import telemetry

        telemetry.event(
            "rank", "failure",
            rank=err.rank, reason=err.reason,
            detected_by=self.rank, exit_code=EXIT_RANK_FAILURE,
        )
        if self.report_dir:
            payload = {
                "failed_rank": err.rank,
                "reason": err.reason,
                "detected_by": self.rank,
                "suspects": err.suspects,
                "watchdog_timeout": self.timeout,
                "exit_code": EXIT_RANK_FAILURE,
                "wall_time": _time.time(),
                "resume": "--resume auto",
            }
            try:
                tmp = _os.path.join(
                    self.report_dir,
                    f"rank_failure_p{self.rank}.json.tmp",
                )
                with open(tmp, "w") as f:
                    _json.dump(payload, f, indent=2)
                _os.replace(tmp, tmp[: -len(".tmp")])
            except OSError:
                pass  # forensics must never mask the abort itself
        telemetry.get_sink().flush()

    def _fire(self, err: RankFailureError) -> None:
        if self._on_failure is not None:
            self._on_failure(err)
            return
        # Default: the main thread may be unreachable (wedged in a
        # gloo/ICI collective) — report, flush, and hard-exit with the
        # documented code so the survivor never hangs past the timeout.
        self.report(err)
        import sys as _sys

        print(f"watchdog: {err}; exiting {EXIT_RANK_FAILURE}",
              file=_sys.stderr, flush=True)
        from multigpu_advectiondiffusion_tpu import telemetry

        telemetry.get_sink().close()
        _os._exit(EXIT_RANK_FAILURE)


@_contextlib.contextmanager
def watchdog_scope(watchdog: Optional[RankWatchdog]):
    """Run a block under an (optional) started + installed watchdog.

    On an exception inside the block, if the watchdog has (or now
    finds) a dead/stalled peer, the exception is converted to the
    structured :class:`RankFailureError` — a gloo "connection reset"
    racing the monitor thread classifies as the rank failure it is
    instead of a generic exit 1.
    """
    if watchdog is None:
        yield None
        return
    watchdog.start()
    install_watchdog(watchdog)
    try:
        yield watchdog
    except RankFailureError as exc:
        watchdog.report(exc)  # e.g. a timeout-wrapped barrier fired
        raise
    except Exception as exc:
        # wait up to one staleness window: the exception usually beats
        # the heartbeat evidence (and a SIGKILLed peer may still be an
        # unreaped zombie whose pid probes alive)
        err = watchdog.failure or watchdog.await_verdict()
        if err is not None:
            watchdog.report(err)
            raise err from exc
        raise
    finally:
        install_watchdog(None)
        watchdog.stop()
