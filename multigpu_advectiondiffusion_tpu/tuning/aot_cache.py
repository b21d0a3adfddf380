"""Persistent AOT executable cache: a repeat request never recompiles.

The batched ensemble engine makes one compiled executable serve B
members — this module makes it serve every *process* that asks for the
same program again. Keyed like the tuner's decision cache
(``tuning/cache.py``) plus the program axes the tuner abstracts over —
``(solver, shape, dtype, mesh, impl, steps_per_exchange, program key
incl. the ensemble B, argument avals, backend/device kind, jax
version)`` — each entry is one ``jax.experimental.serialize_executable``
blob written atomically (tempfile + ``os.replace``, the
``tuning/cache.py`` discipline). A corrupt, stale (different jax/
backend/devices) or mismatched entry is a MISS, never a crash.

Wired through ``models/base.SolverBase._compiled`` ->
``telemetry/xprof.wrap_dispatch``: on the first call of a dispatch
program the introspection wrapper consults this store before paying
``lower().compile()``; a hit deserializes the executable (milliseconds)
and the ``xla:cost`` event records ``compile_seconds_saved`` — the
compile seconds the original build paid, now skipped. Every lookup is
an ``aot_cache:{hit,miss}`` event and every write an
``aot_cache:store``, so a warm run is auditable from the stream
(``out/ensemble_gate.sh`` gates exactly that).

Opt-in: set ``TPUCFD_AOT_CACHE=DIR`` (or the CLI ``--aot-cache DIR`` /
:func:`configure`) — executables are per-machine artifacts, so the
store never engages implicitly.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
import time
from typing import Optional

AOT_SCHEMA = 1
ENV_PATH = "TPUCFD_AOT_CACHE"

# process-wide configuration (the CLI writes it before building
# solvers); the env var is the fallback, like the tuning cache
_state = {"dir": None, "enabled": None}


def configure(cache_dir: Optional[str] = None,
              enabled: Optional[bool] = None) -> None:
    """Set the process-wide AOT-cache knobs; ``None`` leaves one as-is.
    Pointing at a directory implies enablement."""
    if cache_dir is not None:
        _state["dir"] = cache_dir
        if enabled is None and _state["enabled"] is None:
            _state["enabled"] = True
    if enabled is not None:
        _state["enabled"] = bool(enabled)


def cache_dir() -> Optional[str]:
    return _state["dir"] or os.environ.get(ENV_PATH) or None


def enabled() -> bool:
    if _state["enabled"] is not None:
        return _state["enabled"] and cache_dir() is not None
    return bool(cache_dir())


def _emit(name: str, **fields) -> None:
    from multigpu_advectiondiffusion_tpu import telemetry

    telemetry.event("aot_cache", name, **fields)


def _environment_facts() -> dict:
    """Everything about THIS process a serialized executable is only
    valid under — a mismatch on load is staleness, i.e. a miss."""
    import jax

    try:
        kinds = sorted({d.device_kind for d in jax.local_devices()})
    except Exception:  # noqa: BLE001 — facts degrade, never crash
        kinds = []
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kinds": kinds,
        "process_count": jax.process_count(),
    }


def _physics_fingerprint(cfg) -> str:
    """Hash of the config fields that bake into a compiled program's
    CONSTANTS (diffusivity/nu/cfl/bc/weno/...). The tuner's key
    deliberately abstracts over physics scalars — two runs differing
    only in K share a kernel *choice* — but they do NOT share an
    *executable*: dt (= c·dx²/K for diffusion) is a compiled-in
    constant, so a K=0.7 run deserializing a K=1.0 blob would march
    the wrong clock. Same skip set as ``cli.drivers.physics_meta``
    plus the grid (its shape already keys via the tuner/avals)."""
    import dataclasses
    import json

    skip = {"grid", "ic", "ic_params", "impl", "overlap",
            "steps_per_exchange", "exchange"}
    out = {}
    for f in dataclasses.fields(cfg):
        if f.name in skip:
            continue
        v = getattr(cfg, f.name)
        if isinstance(v, tuple):
            v = list(v)
        try:
            json.dumps(v)
        except TypeError:
            continue  # non-serializable (callable source term): unkeyed
        out[f.name] = v
    body = json.dumps(out, sort_keys=True)
    return hashlib.sha256(body.encode()).hexdigest()[:16]


def dispatch_key(solver, program_key, steps=None,
                 donate: bool = False) -> str:
    """The cache key for one dispatch-cache entry: the tuner's config
    key (solver, shape, dtype, integrator, mesh, backend — and, for the
    ensemble programs, the member count B riding ``program_key``) plus
    the physics fingerprint (scalars like K/nu/cfl compile into the
    executable as constants — the cross-job sharing the scheduler's
    per-root cache makes possible is exactly where that collision
    bites), the program identity and the compile-relevant kernel
    knobs. The caller (``xprof``) appends the argument-aval
    fingerprint at first call, when the concrete operands exist."""
    import jax

    from multigpu_advectiondiffusion_tpu.tuning.autotuner import make_key

    try:
        base = make_key(
            type(solver), solver.cfg, solver.mesh, solver.decomp,
            jax.default_backend(),
        )
    except Exception:  # noqa: BLE001 — an unkeyable config just misses
        base = type(solver).__name__
    try:
        phys = _physics_fingerprint(solver.cfg)
    except Exception:  # noqa: BLE001 — an unkeyable config just misses
        phys = "?"
    # storage dtype + compensation carry (ISSUE 16): the tuner key
    # (``base``) already separates precision modes, but the carry
    # toggle (core.dtypes.bf16_carry_enabled) changes the compiled
    # generic-loop program WITHOUT changing the config — an entry
    # compiled carry-on must never be served to a carry-off process
    storage = getattr(solver, "storage_dtype", None)
    storage = str(storage) if storage is not None else str(
        getattr(solver.cfg, "dtype", "?")
    )
    carry = int(bool(getattr(solver, "_bf16_carry", True)))
    return "|".join([
        base,
        f"impl={getattr(solver.cfg, 'impl', 'xla')}",
        f"k={int(getattr(solver.cfg, 'steps_per_exchange', 1) or 1)}",
        f"ex={getattr(solver.cfg, 'exchange', 'collective')}",
        f"storage={storage}",
        f"carry={carry}",
        f"phys={phys}",
        f"prog={program_key}",
        f"steps={steps}",
        # buffer donation (ISSUE 19): a donated program aliases its
        # state operand into the output — a different executable than
        # the undonated build, so the bit is part of the identity (a
        # donated blob deserialized into an undonated dispatch would
        # free buffers the caller still holds)
        f"donate={int(bool(donate))}",
    ])


def aval_fingerprint(args) -> str:
    """Shape/dtype fingerprint of the call's operand pytree — the same
    program key with different avals is a different executable."""
    import jax

    leaves = jax.tree_util.tree_leaves(args)
    return ";".join(
        f"{tuple(getattr(a, 'shape', ()))}:"
        f"{getattr(getattr(a, 'dtype', None), 'name', type(a).__name__)}"
        for a in leaves
    )


def _entry_path(root: str, key: str) -> str:
    h = hashlib.sha256(key.encode()).hexdigest()[:32]
    return os.path.join(root, f"{h}.aot")


def load(key: str, args):
    """Resolve ``key`` (+ the args' aval fingerprint) against the
    store. Returns ``(compiled, meta)`` on a hit, ``None`` on any kind
    of miss — absent, corrupt, stale environment, mismatched key or
    avals, or a deserialization failure. Emits ``aot_cache:{hit,miss}``
    either way."""
    root = cache_dir()
    if not root:
        return None
    path = _entry_path(root, key)
    t0 = time.perf_counter()
    try:
        with open(path, "rb") as f:
            entry = pickle.load(f)
    except FileNotFoundError:
        _emit("miss", key=key, reason="absent", path=path)
        return None
    except Exception as exc:  # noqa: BLE001 — corrupt entry = miss
        _emit("miss", key=key, reason=f"corrupt: {exc}"[:200], path=path)
        return None
    try:
        if entry.get("schema") != AOT_SCHEMA:
            raise ValueError(f"schema {entry.get('schema')}")
        if entry.get("key") != key:
            raise ValueError("key hash collision")
        env = _environment_facts()
        if entry.get("environment") != env:
            raise ValueError(
                f"stale environment {entry.get('environment')} != {env}"
            )
        fp = aval_fingerprint(args)
        if entry.get("avals") != fp:
            raise ValueError("operand avals differ")
        from jax.experimental import serialize_executable as se

        blob, in_tree, out_tree = entry["payload"]
        compiled = se.deserialize_and_load(
            blob, in_tree, out_tree,
            execution_devices=_execution_devices(args),
        )
    except Exception as exc:  # noqa: BLE001 — stale entry = miss
        _emit("miss", key=key, reason=f"stale: {exc}"[:200], path=path)
        return None
    meta = {
        "compile_seconds_saved": float(entry.get("compile_seconds", 0.0)),
        "load_seconds": time.perf_counter() - t0,
        "path": path,
    }
    _emit(
        "hit", key=key, path=path,
        load_seconds=round(meta["load_seconds"], 6),
        compile_seconds_saved=round(meta["compile_seconds_saved"], 6),
    )
    return compiled, meta


def _execution_devices(args) -> list:
    """The devices the operands live on, in their sharding's order (the
    first device for abstract operands without one): a deserialized
    executable otherwise loads onto every device of the backend and
    refuses a single-device call on a multi-device host."""
    import jax

    for leaf in jax.tree.leaves(args):
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            return list(sharding._device_assignment)
    return [jax.devices()[0]]


def store(key: str, args, compiled, compile_seconds: float) -> bool:
    """Serialize ``compiled`` under ``key`` with an atomic replace;
    failures are recorded (``aot_cache:store`` with
    ``persisted=False``), never raised — a backend that cannot
    serialize degrades to the plain compile-every-process behavior."""
    root = cache_dir()
    if not root:
        return False
    path = _entry_path(root, key)
    try:
        from jax.experimental import serialize_executable as se

        payload = se.serialize(compiled)
        entry = {
            "schema": AOT_SCHEMA,
            "key": key,
            "environment": _environment_facts(),
            "avals": aval_fingerprint(args),
            "compile_seconds": float(compile_seconds),
            "created": time.time(),
            "payload": payload,
        }
        os.makedirs(root, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=root, prefix=".aot_",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                pickle.dump(entry, f)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):  # replace failed
                os.unlink(tmp)
    except Exception as exc:  # noqa: BLE001
        _emit("store", key=key, persisted=False,
              reason=f"{type(exc).__name__}: {exc}"[:200])
        return False
    _emit("store", key=key, persisted=True, path=path,
          bytes=os.path.getsize(path),
          compile_seconds=round(float(compile_seconds), 6))
    return True
