"""Perf-evidence harness: registered benchmark rungs that cannot kill a run.

Round 5's verdict ranked the evidential gap first: `BENCH_r05.json` is a
stack trace (rc=1) because `bench.py` had no backend-unavailable handling
and no partial artifacts — one failed rung destroyed every measurement.
This module is the fix, in the shape MLPerf-style loggers and Prometheus
client libraries standardize (PAPERS.md): every rung is an isolated,
registered callable that ALWAYS produces one schema-stable JSON record

    {"rung": str, "ok": bool, "device": str, "elapsed_s": float,
     "value": {...}}                      # ok
    {"rung": ..., "ok": false, "reason"|"error": str, ...}  # degraded

Backend probing happens ONCE, first (`probe_backend` — a raising
`jax.devices` is an answer, not a crash); TPU-only rungs degrade to
``reason: "backend_unavailable"`` and CPU-salvageable rungs still run, so
a run with no chip still emits real dispatch/serving/ring measurements.
`regression_check` diffs the run against the newest ``BENCH_r*.json``
artifact and separates code regressions from launch-window artifacts.

`bench.py` at the repo root registers the actual rungs and drives this.
"""

from __future__ import annotations

import glob
import json
import os
import time
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Any, Callable, Dict, List, Optional, Sequence

from . import flight_recorder as _flight
from . import metrics as _metrics

__all__ = ["Rung", "register_rung", "rung_names", "get_rung",
           "probe_backend", "run_rung", "run", "select",
           "validate_record", "regression_check", "SCHEMA",
           "BackendUnavailable"]

SCHEMA = "paddle_tpu.bench/v1"


@dataclass
class Rung:
    """One registered benchmark rung.

    ``fn(ctx)`` receives a SimpleNamespace with ``smoke`` (bool),
    ``on_tpu`` (bool), ``probe`` (the backend probe dict) and
    ``device_kind`` (str) — rungs read the backend from the ctx instead
    of probing jax themselves, so one broken backend query can't take
    down every rung.  It returns a JSON-able dict of measurements (the
    record's ``value``) or raises; either way the harness emits a record.
    """

    name: str
    fn: Callable[[SimpleNamespace], Optional[Dict[str, Any]]]
    requires: str = "any"           # "any" (CPU-salvageable) | "tpu"
    est_cold_s: float = 60.0        # worst-case cold cost (budget gate)
    smoke: bool = False             # included in --smoke runs


_REGISTRY: Dict[str, Rung] = {}


def register_rung(name: str, *, requires: str = "any",
                  est_cold_s: float = 60.0, smoke: bool = False):
    """Decorator: register ``fn(ctx) -> dict`` as a rung."""
    if requires not in ("any", "tpu"):
        raise ValueError(f"requires must be 'any' or 'tpu', got {requires!r}")

    def deco(fn):
        _REGISTRY[name] = Rung(name, fn, requires, est_cold_s, smoke)
        return fn
    return deco


def rung_names() -> List[str]:
    return list(_REGISTRY)


def get_rung(name: str) -> Rung:
    return _REGISTRY[name]


def probe_backend() -> Dict[str, Any]:
    """One up-front backend query; a raising `jax.devices` (no chip, a
    chip another process holds, bad env) is captured as data."""
    out: Dict[str, Any] = {"ok": False, "platform": None,
                           "device_kind": None, "n_devices": 0,
                           "error": None}
    try:
        import jax
        devs = jax.devices()
        d = devs[0]
        out.update(ok=True, platform=d.platform,
                   device_kind=str(getattr(d, "device_kind", d.platform)),
                   n_devices=len(devs))
    except Exception as e:  # noqa: BLE001 - the whole point
        out["error"] = repr(e)[:300]
    return out


# Backend-INIT failure fingerprints (ISSUE 6 satellite / ROADMAP
# housekeeping): BENCH_r05 died rc=1 because PJRT `make_c_api_client`
# failed inside a rung AFTER the probe — the error class is
# environmental (no chip reachable), so the record must say
# `backend_unavailable` like the probe-gated rungs, not `error`.
_BACKEND_INIT_TYPES = ("RuntimeError", "XlaRuntimeError",
                       "JaxRuntimeError", "InternalError")
_BACKEND_INIT_MARKERS = ("make_c_api_client", "Unable to initialize backend",
                         "failed to initialize backend",
                         "No visible device", "no backend",
                         "Failed to get global TPU topology",
                         "PJRT_Client_Create", "DEADLINE_EXCEEDED: Failed "
                         "to connect")


class BackendUnavailable(RuntimeError):
    """Raise from INSIDE a rung body when the backend/toolchain the
    rung measures is absent — e.g. a jax build without Pallas for the
    kernel rungs: the record degrades to ``ok: false,
    reason: "backend_unavailable"`` exactly like the probe-gated
    TPU-only rungs, instead of counting as a code error (rc=1)."""


def is_backend_init_error(e: BaseException) -> bool:
    """True when an exception is a backend/PJRT initialization failure
    rather than a bug inside the rung."""
    if isinstance(e, BackendUnavailable):
        return True
    if type(e).__name__ not in _BACKEND_INIT_TYPES:
        return False
    msg = str(e)
    return any(m in msg for m in _BACKEND_INIT_MARKERS)


def _ctx(probe: Dict[str, Any], smoke: bool) -> SimpleNamespace:
    return SimpleNamespace(
        smoke=smoke, probe=probe,
        on_tpu=bool(probe["ok"] and probe["platform"] == "tpu"),
        device_kind=probe["device_kind"] or probe["platform"]
        or "unavailable")


def run_rung(rung: Rung, probe: Optional[Dict[str, Any]] = None,
             smoke: bool = False,
             budget_left: Optional[Callable[[], float]] = None,
             collect_metrics: bool = False) -> Dict[str, Any]:
    """Run one rung in isolation; always returns a schema-valid record.

    With ``collect_metrics`` the registry is reset before the rung and
    snapshotted after, so the record carries the rung's OWN metric
    deltas under a ``metrics`` key — every BENCH artifact then
    self-evidences what actually ran (ISSUE 2): a tokens/sec claim sits
    next to the dispatch/collective/serving counters it produced.
    """
    if probe is None:
        probe = probe_backend()
    ctx = _ctx(probe, smoke)
    base = {"rung": rung.name, "device": ctx.device_kind, "elapsed_s": 0.0}
    if rung.requires == "tpu" and not ctx.on_tpu:
        return dict(base, ok=False, reason="backend_unavailable")
    if smoke and not rung.smoke:
        return dict(base, ok=False, reason="skipped_smoke")
    if budget_left is not None and budget_left() < rung.est_cold_s:
        return dict(base, ok=False, reason="budget",
                    remaining_s=round(budget_left(), 1),
                    est_cold_s=rung.est_cold_s)
    if collect_metrics:
        _metrics.reset()
        from . import compile_tracker as _compile
        _compile.reset()
    _flight.default_recorder().record_event("rung_begin", rung=rung.name)
    t0 = time.perf_counter()
    try:
        value = rung.fn(ctx)
        rec = dict(base, ok=True,
                   value=value if isinstance(value, dict)
                   else {"result": value})
    except (KeyboardInterrupt, SystemExit):
        raise                   # the operator's abort outranks degradation
    except BaseException as e:  # noqa: BLE001 - a rung must never kill a run
        err = f"{type(e).__name__}: {e}"[:500]
        if is_backend_init_error(e):
            # a dead/unreachable backend discovered mid-rung is the same
            # ANSWER as a failed probe: degrade, don't report a code bug
            rec = dict(base, ok=False, reason="backend_unavailable",
                       error=err)
        else:
            rec = dict(base, ok=False, error=err)
        _flight.default_recorder().record_event(
            "rung_error", rung=rung.name, error=err[:300])
    rec["elapsed_s"] = round(time.perf_counter() - t0, 3)
    if collect_metrics:
        rec["metrics"] = _metrics.snapshot()
        from . import compile_tracker as _compile
        if _compile.total_compiles():
            # before/after evidence for the ROADMAP item-1 cache/AOT
            # work: what this rung compiled, for how long, and why
            rec["compile_report"] = _compile.compile_report()
    return rec


def select(names: Optional[Sequence[str] | str]) -> List[Rung]:
    """Resolve a rung selection: None/'all' = everything, 'cpu' = the
    CPU-salvageable set (requires == 'any'), 'tpu' = TPU-only rungs, or
    an explicit comma-separated / list of rung names."""
    if names is None or names == "all":
        return list(_REGISTRY.values())
    if isinstance(names, str):
        if names == "cpu":
            return [r for r in _REGISTRY.values() if r.requires == "any"]
        if names == "tpu":
            return [r for r in _REGISTRY.values() if r.requires == "tpu"]
        names = [n.strip() for n in names.split(",") if n.strip()]
    unknown = [n for n in names if n not in _REGISTRY]
    if unknown:
        raise KeyError(f"unknown rungs {unknown}; have {rung_names()}")
    return [_REGISTRY[n] for n in names]


def run(names: Optional[Sequence[str] | str] = None, smoke: bool = False,
        budget_left: Optional[Callable[[], float]] = None,
        emit: Optional[Callable[[Dict[str, Any]], None]] = None,
        probe: Optional[Dict[str, Any]] = None,
        release: Optional[Callable[[], None]] = None,
        collect_metrics: bool = False) -> List[Dict[str, Any]]:
    """Run a selection of rungs; returns their records in order.  ``emit``
    is called per record as it lands (streaming JSON lines); ``release``
    runs between rungs (device-memory cleanup); ``collect_metrics``
    attaches each rung's own registry delta to its record."""
    if probe is None:
        probe = probe_backend()
    records = []
    for rung in select(names):
        rec = run_rung(rung, probe, smoke, budget_left,
                       collect_metrics=collect_metrics)
        records.append(rec)
        if emit is not None:
            emit(rec)
        # release after every rung that actually RAN — including failed
        # ones (an OOM'd rung leaving its buffers pinned would cascade
        # into every later rung); gate-skipped records did no device work
        if release is not None and (rec.get("ok") or "error" in rec):
            try:
                release()
            except Exception:  # noqa: BLE001 - cleanup is best-effort
                pass
    return records


def validate_record(rec: Any) -> Optional[str]:
    """Schema check; returns None when valid, else a reason string."""
    if not isinstance(rec, dict):
        return "record is not an object"
    if not isinstance(rec.get("rung"), str) or not rec["rung"]:
        return "missing rung name"
    if not isinstance(rec.get("ok"), bool):
        return "missing ok flag"
    if not isinstance(rec.get("device"), str):
        return "missing device"
    if not isinstance(rec.get("elapsed_s"), (int, float)):
        return "missing elapsed_s"
    if rec["ok"]:
        if not isinstance(rec.get("value"), dict):
            return "ok record without value object"
    else:
        if not (isinstance(rec.get("reason"), str)
                or isinstance(rec.get("error"), str)):
            return "degraded record without reason/error"
    try:
        json.dumps(rec)
    except (TypeError, ValueError):
        return "record is not JSON-serializable"
    return None


# --------------------------------------------------------------- regression

def _parse_artifact_tail(path: str) -> Dict[str, Dict[str, Any]]:
    """Previous-round records by rung name.  Handles both artifact
    generations: legacy lines ``{"bench": name, metric: ...}`` and harness
    lines ``{"rung": name, "value": {...}}``."""
    try:
        doc = json.load(open(path))
    except Exception:  # noqa: BLE001
        return {}
    lines = []
    if isinstance(doc, dict) and isinstance(doc.get("tail"), str):
        lines = doc["tail"].splitlines()
    elif isinstance(doc, dict) and isinstance(doc.get("records"), list):
        return {r["rung"]: dict(r.get("value") or {})
                for r in doc["records"]
                if isinstance(r, dict) and r.get("ok") and r.get("rung")}
    out: Dict[str, Dict[str, Any]] = {}
    for line in lines:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            d = json.loads(line)
        except json.JSONDecodeError:
            continue
        if not isinstance(d, dict):
            continue
        if "bench" in d:
            out[d["bench"]] = d
        elif d.get("rung") and d.get("ok") and isinstance(
                d.get("value"), dict):
            out[d["rung"]] = dict(d["value"])
    return out


def latest_artifact(repo_dir: Optional[str] = None) -> Optional[str]:
    if repo_dir is None:
        repo_dir = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    arts = sorted(glob.glob(os.path.join(repo_dir, "BENCH_r*.json")))
    return arts[-1] if arts else None


def regression_check(current: Sequence[Dict[str, Any]],
                     previous: Optional[str] = None,
                     keys: Optional[Dict[str, str]] = None,
                     env_probe: Optional[Dict[str, Any]] = None
                     ) -> Optional[Dict[str, Any]]:
    """Per-rung relative deltas against the previous official artifact.

    ``current`` is this run's harness records; ``previous`` a path to a
    BENCH_*.json (default: newest in the repo); ``keys`` maps rung name ->
    higher-is-better metric key (or a sequence of them — the first
    labels the rung, the rest report as ``<rung>.<key>``).  Separates
    code regressions from
    launch-window artifacts the way round 4/5 learned to (a latency-bound
    rung whose drop tracks the dispatch-floor worsening is ENV-SUSPECT,
    not a regression).
    """
    keys = keys or {}
    if previous is None:
        previous = latest_artifact()
    if previous is None:
        return None
    prev = _parse_artifact_tail(previous)
    cur_by_name: Dict[str, Dict[str, Any]] = {}
    for rec in current:
        if rec.get("ok") and isinstance(rec.get("value"), dict):
            cur_by_name[rec["rung"]] = rec["value"]
    if env_probe is None:
        env_probe = cur_by_name.get("env_probe", {})
    deltas: Dict[str, float] = {}
    rung_of: Dict[str, str] = {}
    for name, keyspec in keys.items():
        # a rung may own several regression keys (e.g. spec_decode's
        # speedup AND weight ratio): the first labels the rung itself,
        # the rest label as "<rung>.<key>"
        key_list = ((keyspec,) if isinstance(keyspec, str)
                    else tuple(keyspec))
        if name not in cur_by_name or name not in prev:
            continue
        for i, key in enumerate(key_list):
            if key not in cur_by_name[name] or key not in prev[name]:
                continue
            label = name if i == 0 else f"{name}.{key}"
            old = float(prev[name][key])
            new = float(cur_by_name[name][key])
            if old > 0:
                deltas[label] = round((new - old) / old, 4)
                rung_of[label] = name
    if not deltas:
        return None
    prev_env = prev.get("env_probe", {})
    regressed, env_suspect = [], {}
    floor = (env_probe or {}).get("dispatch_floor_ms")
    pfloor = prev_env.get("dispatch_floor_ms")
    ptf = prev_env.get("matmul_tflops")
    tf = (env_probe or {}).get("matmul_tflops")
    for name, v in sorted(deltas.items()):
        if v >= -0.03:
            continue
        cur = cur_by_name[rung_of[name]]
        reason = None
        if cur.get("latency_bound") and floor:
            if pfloor:
                floor_worsening = (floor - pfloor) / pfloor
            else:
                # no previous probe: a floor far above the quiet-window
                # ~1.5 ms is the explanation
                floor_worsening = (floor - 1.5) / 1.5
            if floor_worsening > -v / 2:
                reason = (f"latency-bound rung; dispatch floor {floor} ms "
                          f"vs prev {pfloor if pfloor else '~1.5 (quiet)'}"
                          " ms")
        if reason is None and ptf and tf and tf < 0.85 * ptf:
            reason = f"chip window degraded: {tf} vs {ptf} TFLOP/s"
        if reason is None and pfloor and floor and floor > 1.15 * pfloor:
            reason = f"dispatch floor degraded: {floor} vs {pfloor} ms"
        if reason:
            env_suspect[name] = reason
        else:
            regressed.append(name)
    return {"vs": os.path.basename(previous), "rel_delta": deltas,
            "env": env_probe or None,
            "regressed": regressed, "env_suspect": env_suspect}
