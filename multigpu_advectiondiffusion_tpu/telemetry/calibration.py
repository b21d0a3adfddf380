"""Measured-peak calibration: persisted demonstrated-capability rates.

The cost model's peak bandwidth/FLOP rates were *assumed* — per-device-kind
datasheet rows, env-overridable (``costmodel.PEAKS``). Every
roofline percentage, every tuner pruning decision read against them.
This store closes the modeled-vs-measured gap the reference closed with
``nvprof`` counters: once a run's compiled executables report their own
XLA bytes/FLOPs (:mod:`telemetry.xprof`), the achieved bandwidth of the
run's *binding* resource is a measured lower bound on the hardware's
real, attainable peak — on a shared or a thermally limited chip, a
far more honest pruning denominator than the datasheet number.

Semantics — **demonstrated capability, max-merge**:

* :func:`observe` folds one run's achieved rate into the record for the
  device kind, keeping the MAX ever observed (a slow run never
  lowers the calibrated peak below a faster earlier one);
* :func:`lookup` returns the record the cost model consults —
  ``costmodel.peak_rates`` applies it OVER the env-assumed peaks
  (measured beats assumed; delete the file or set
  ``TPUCFD_CALIBRATION_PATH=off`` to fall back to assumptions);
* roofline percentages read against a calibrated peak are *relative to
  what the rig has demonstrated*, not to a datasheet — a later, faster
  run can momentarily read >100% until its own observation lands.

The record is an atomic JSON file keyed like the tuner's decision cache
(``tuning/cache.py`` discipline: tempfile + ``os.replace``,
read-modify-write under a process lock; corrupt file == empty, never a
crash). Default location sits next to the tuning cache;
``TPUCFD_CALIBRATION_PATH`` overrides (``off``/``0``/empty disables the
subsystem entirely). Every persisted update is a ``calib:update``
telemetry event, so a tuned/roofline number is auditable back to the
run that calibrated its denominator.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Optional

CALIBRATION_SCHEMA = 1

ENV_PATH = "TPUCFD_CALIBRATION_PATH"
_DEFAULT_PATH = os.path.join(
    "~", ".cache", "multigpu_advectiondiffusion_tpu", "calibration.json"
)

_lock = threading.Lock()


def default_path() -> Optional[str]:
    """The store's file path, or ``None`` when calibration is disabled
    (``TPUCFD_CALIBRATION_PATH`` set to ``off``/``0``/empty)."""
    env = os.environ.get(ENV_PATH)
    if env is not None:
        if env.strip().lower() in ("", "0", "off", "none", "disabled"):
            return None
        return env
    return os.path.expanduser(_DEFAULT_PATH)


def _read(path: str) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
    except FileNotFoundError:
        return {}
    except (json.JSONDecodeError, OSError, ValueError):
        return {}  # corrupt/truncated: a miss, not a crash
    if not isinstance(data, dict) or data.get("schema") != CALIBRATION_SCHEMA:
        return {}
    entries = data.get("entries")
    return entries if isinstance(entries, dict) else {}


def _write(path: str, entries: dict) -> None:
    payload = {"schema": CALIBRATION_SCHEMA, "entries": entries}
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".calib_", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):  # replace failed
            os.unlink(tmp)


def lookup(kind: str, path: Optional[str] = None) -> Optional[dict]:
    """The calibration record for a ``device_kind`` (``cpu``,
    ``TPU v5 lite``, ...), or ``None`` when absent or the subsystem is
    disabled. Keys: ``bytes_per_s``/``flops_per_s`` (max observed;
    either may be absent), ``samples``, ``updated`` (epoch), ``run``
    provenance of the last improving observation."""
    path = path if path is not None else default_path()
    if not path:
        return None
    with _lock:
        entry = _read(path).get(kind)
    return dict(entry) if isinstance(entry, dict) else None


def observe(
    kind: str,
    bytes_per_s: Optional[float] = None,
    flops_per_s: Optional[float] = None,
    run: Optional[str] = None,
    path: Optional[str] = None,
) -> Optional[dict]:
    """Fold one run's achieved rate(s) into the device kind's record
    (max-merge) and persist atomically; returns the updated record, or
    ``None`` when disabled / nothing to record. Emits one
    ``calib:update`` event (``persisted`` says whether a peak actually
    improved)."""
    path = path if path is not None else default_path()
    if not path or (not bytes_per_s and not flops_per_s):
        return None
    with _lock:
        entries = _read(path)
        entry = dict(entries.get(kind) or {})
        improved = False
        for key, val in (("bytes_per_s", bytes_per_s),
                         ("flops_per_s", flops_per_s)):
            if val is None or val <= 0:
                continue
            if float(val) > float(entry.get(key) or 0.0):
                entry[key] = float(val)
                improved = True
        entry["samples"] = int(entry.get("samples") or 0) + 1
        if improved:
            entry["updated"] = time.time()
            if run:
                entry["run"] = run
        entries[kind] = entry
        _write(path, entries)
    from multigpu_advectiondiffusion_tpu import telemetry

    telemetry.event(
        "calib", "update",
        device_kind=kind,
        bytes_per_s=entry.get("bytes_per_s"),
        flops_per_s=entry.get("flops_per_s"),
        samples=entry["samples"],
        persisted=improved,
        path=path,
    )
    return dict(entry)
