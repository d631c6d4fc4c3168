"""Engine X-ray: the per-compiled-program execution ledger.

ISSUE 14 tentpole — the runtime twin of the compile tracker (PR 6):
where `compile_tracker` answers *who compiled, how long, and why*, this
module answers *who executes, how often, for how much device time, at
what achieved FLOP/s*.  Every program routed through
``compile_tracker.wrap_first_call`` (the serving tick / spec_tick /
prefill buckets / prefill_cont / cow grid, the fused optimizer step)
registers a :class:`ProgramEntry` keyed by the compile-tracker name plus
its scalar blame pairs — ``serving.tick[steps_per_tick=2,...]``,
``serving.prefill[L_pad=64,...]`` — and every dispatch counts here.

Three layers of evidence per program:

* **Dispatch counts** — always on; one attribute increment plus a
  (metrics-gated) counter bump per call.
* **Sampled device wall time** — ``FLAGS_xray_sample_interval`` (default
  0 = off): every Nth dispatch runs a SYNCED timing probe —
  ``jax.block_until_ready`` on the program outputs before the stop
  clock read (graft-lint R006's contract; an unsynced interval would
  time the async enqueue, not the compute).  Unsampled dispatches stay
  fully async, and the serving engine forces a real tick-loop boundary
  whenever the next chained dispatch would be sampled, so the
  double-buffered overlap path is never measured through a chain (a
  chained probe would charge the predecessor's compute to this
  program).
* **Static cost** — ``ServingEngine.warmup()``'s AOT path hands each
  program's jax ``Lowered`` to :func:`attach_lowered`:
  ``cost_analysis()`` FLOPs / bytes-accessed, plus a custom-call scan
  of the lowered text for the kernel-coverage audit.  NOTE what
  cost_analysis counts: HLO-level FLOPs of everything in the program
  (attention, layernorm, sampling, dequant — not the 6N "model FLOPs"
  convention of :mod:`.flops`), so per-program MFU here reads as
  achieved-vs-peak for the program as lowered, slightly above a
  model-FLOPs MFU for the same throughput.

Joining the three gives the ledger row: mean sampled seconds,
extrapolated total device seconds (mean x dispatches),
fraction-of-total-device-time, achieved FLOP/s and MFU against the
:func:`.flops.peak_flops` table.

The kernel-coverage audit (:func:`kernel_coverage`) reports, per
audited program, whether the hot path runs a Pallas kernel — and HOW
it knows.  Two evidence channels: the custom-call scan of the lowered
HLO (``via: "custom_call"`` — the TPU case), and trace-time **kernel
claims** (``via: "interpret"``): interpret-mode ``pallas_call`` lowers
to a plain ``stablehlo.while`` with no custom-call marker, so each
kernel wrapper calls :func:`claim_kernel` while tracing and the
warmup's AOT path brackets ``lower()`` with
:func:`capture_kernel_claims` to collect them.  A program with neither
channel reporting a kernel carries the explicit dense-gather note
(ROADMAP 5b suspects: suffix prefill, spec verify, MoE dispatch).

Readout everywhere the repo already exports: the
``xray.program_dispatches_total`` / ``xray.program_device_seconds_total``
counters and per-program ``xray.program_mfu`` gauges on ``/metrics``,
``ServingEngine.stats()["xray"]``, flight-recorder snapshots, and
``python -m paddle_tpu.observability.dump --xray``.
"""

from __future__ import annotations

import contextlib
import re
import threading
import time
from typing import Any, Dict, List, Optional

import jax

from . import flops as _flops
from . import metrics as _metrics

__all__ = ["ProgramEntry", "register", "dispatch", "sample_due",
           "sampling_on", "sample_interval", "attach_lowered", "get",
           "ledger", "kernel_coverage", "report", "reset", "key_for",
           "claim_kernel", "capture_kernel_claims"]

_M_DISPATCHES = _metrics.counter(
    "xray.program_dispatches_total", "compiled-program dispatches by the "
    "engine X-ray ledger, labelled program= (the compile-tracker name "
    "plus its scalar blame pairs)")
_M_DEVICE_S = _metrics.counter(
    "xray.program_device_seconds_total", "cumulative SAMPLED synced "
    "wall seconds per compiled program (every "
    "FLAGS_xray_sample_interval-th dispatch blocks on its outputs); "
    "multiply the mean sample by program_dispatches_total for the "
    "extrapolated total the dump --xray report shows")
_M_MFU = _metrics.gauge(
    "xray.program_mfu", "per-program model-FLOPs utilization of the "
    "most recent sampled dispatch window: cost_analysis() FLOPs over "
    "mean sampled seconds, against the flops.peak_flops table "
    "(HLO-counted FLOPs — see observability/xray.py)")

# Synced from FLAGS_xray_sample_interval (flags.py installs the hook).
_SAMPLE_INTERVAL = 0


def _sync_interval(value) -> None:
    global _SAMPLE_INTERVAL
    _SAMPLE_INTERVAL = max(0, int(value))


def _init_from_flag() -> None:
    try:
        from .. import flags as _flags
        _sync_interval(_flags.get_flag("xray_sample_interval"))
    except Exception:  # noqa: BLE001 - flag not registered yet (early import)
        pass


def sampling_on() -> bool:
    return _SAMPLE_INTERVAL > 0


def sample_interval() -> int:
    return _SAMPLE_INTERVAL


_lock = threading.RLock()
_entries: Dict[str, "ProgramEntry"] = {}

_TARGET_RE = re.compile(r'custom_call_target\s*=\s*"([^"]+)"')
_STABLEHLO_CC_RE = re.compile(r"stablehlo\.custom_call\s*@([\w$.]+)")
_CC_RE = re.compile(r"\bcustom[-_]call\b")
# lowered-text fingerprints of the Pallas/Mosaic kernel path
_PALLAS_MARKERS = ("tpu_custom_call", "pallas", "mosaic", "triton")


class ProgramEntry:
    """One compiled program's ledger row (process-global, like the
    compile tracker: engines with the same configuration share it)."""

    __slots__ = ("key", "name", "label_key", "dispatches", "samples",
                 "sampled_seconds", "min_s", "max_s", "flops",
                 "bytes_accessed", "audited", "custom_calls",
                 "custom_call_targets", "pallas", "kernel_claims")

    def __init__(self, key: str, name: str):
        self.key = key
        self.name = name
        # frozen label key for the hot-path Counter.inc_key (the same
        # cached-key pattern the dispatch loop uses): count() must cost
        # an attribute increment + one gated dict bump, not a kwargs
        # build + sort + cardinality guard per program call
        self.label_key = (("program", key),)
        self.dispatches = 0
        self.samples = 0
        self.sampled_seconds = 0.0
        self.min_s = float("inf")
        self.max_s = 0.0
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.audited = False            # attach_lowered saw its HLO
        self.custom_calls = 0
        self.custom_call_targets: tuple = ()
        self.pallas = False
        self.kernel_claims: tuple = ()  # trace-time (name, mode) pairs


def key_for(name: str, signature: Any = None) -> str:
    """Ledger key: the compile-tracker name plus the SCALAR pairs of its
    blame signature (``serving.tick[steps_per_tick=2,max_batch=4,...]``).
    Non-scalar pair values (the fused step's per-leaf aval tuple, long
    reprs) are dropped — keys must stay readable and bounded."""
    pairs: List[str] = []
    if isinstance(signature, (tuple, list)):
        for item in signature:
            if (isinstance(item, (tuple, list)) and len(item) == 2
                    and isinstance(item[0], str)):
                v = item[1]
                if isinstance(v, bool) or isinstance(v, (int, float)) \
                        or (isinstance(v, str) and len(v) <= 24):
                    pairs.append(f"{item[0]}={v}")
    if not pairs:
        return name
    return name + "[" + ",".join(pairs) + "]"


def register(name: str, signature: Any = None) -> ProgramEntry:
    """Get-or-create the ledger entry for (name, signature) — called by
    ``compile_tracker.wrap_first_call`` for every wrapped program."""
    key = key_for(name, signature)
    with _lock:
        ent = _entries.get(key)
        if ent is None:
            ent = _entries[key] = ProgramEntry(key, name)
        return ent


def get(key: str) -> Optional[ProgramEntry]:
    with _lock:
        return _entries.get(key)


def count(entry: ProgramEntry) -> None:
    """One ledger dispatch (+ the /metrics counter) — the shared
    accounting of :func:`dispatch` and the wrap_first_call compile
    path, so the Prometheus counter always equals the ledger row."""
    entry.dispatches += 1
    _M_DISPATCHES.inc_key(entry.label_key)


def dispatch(entry: ProgramEntry, fn, args, kwargs):
    """Count one dispatch of ``entry``'s program and run it.  Every
    ``FLAGS_xray_sample_interval``-th dispatch is the synced timing
    probe, bracketed on BOTH sides: block_until_ready on the inputs
    before the start clock (pending upstream work — e.g. chunk-prefill
    programs enqueued earlier in the same boundary — must not be
    charged to this program) and on the outputs before the stop clock
    (R006: the sample is device wall time, not enqueue time).
    Unsampled dispatches return the async handles untouched."""
    count(entry)
    iv = _SAMPLE_INTERVAL
    if iv <= 0 or entry.dispatches % iv:
        return fn(*args, **kwargs)
    jax.block_until_ready((args, kwargs))
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    _record_sample(entry, dt)
    return out


def _record_sample(entry: ProgramEntry, dt: float) -> None:
    with _lock:
        entry.samples += 1
        entry.sampled_seconds += dt
        entry.min_s = min(entry.min_s, dt)
        entry.max_s = max(entry.max_s, dt)
        mean = entry.sampled_seconds / entry.samples
    _M_DEVICE_S.inc(dt, program=entry.key)
    peak = _peak()
    if entry.flops and mean > 0 and peak is not None:
        _M_MFU.set(round(entry.flops / mean / peak, 6),
                   program=entry.key)


def sample_due(fn) -> bool:
    """Would the NEXT dispatch of this wrapped program run the synced
    probe?  The serving overlap gate consults this to force a real
    boundary under a due sample (a chained dispatch feeds in-flight
    device handles, so a probe around it would time its predecessor's
    compute too)."""
    entry = getattr(fn, "_xray_entry", None) if fn is not None else None
    iv = _SAMPLE_INTERVAL
    return (entry is not None and iv > 0
            and (entry.dispatches + 1) % iv == 0)


# Trace-time kernel-claims channel: interpret-mode pallas_call leaves
# no custom-call marker in the lowered text (it executes as a
# stablehlo.while), so kernel wrappers record their presence while
# tracing instead.  Thread-local so concurrent warmups don't cross.
_claims_tls = threading.local()


@contextlib.contextmanager
def capture_kernel_claims():
    """Collect :func:`claim_kernel` calls made while tracing inside the
    block; yields the (name, mode) list.  Nestable: the inner capture
    shadows the outer for its extent."""
    prev = getattr(_claims_tls, "claims", None)
    _claims_tls.claims = []
    try:
        yield _claims_tls.claims
    finally:
        _claims_tls.claims = prev


def claim_kernel(name: str, mode: str) -> None:
    """Record that a Pallas kernel was emitted into the program being
    traced (``mode``: "interpret" or "custom_call").  No-op unless a
    :func:`capture_kernel_claims` block is active on this thread."""
    claims = getattr(_claims_tls, "claims", None)
    if claims is not None:
        claims.append((str(name), str(mode)))


def attach_lowered(entry: Optional[ProgramEntry], lowered,
                   claims=None) -> None:
    """Best-effort static cost + kernel info from a jax ``Lowered``
    (the serving warmup's AOT path calls this per grid program), plus
    any trace-time kernel ``claims`` captured around the lower().
    Never raises: an analysis-less backend must not fail warmup."""
    if entry is None or lowered is None:
        return
    if claims is not None:
        # dedupe, preserve first-seen order.  An EMPTY captured list
        # overwrites too: entries are process-global, and a program
        # re-lowered with the kernels flagged off must drop the claims
        # of an earlier build (the audit reports the build, not history)
        entry.kernel_claims = tuple(dict.fromkeys(
            (str(n), str(m)) for n, m in claims))
    try:
        cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        if isinstance(cost, dict):
            f = float(cost.get("flops", 0.0) or 0.0)
            b = float(cost.get("bytes accessed", 0.0) or 0.0)
            if f > 0:
                entry.flops = f
            if b > 0:
                entry.bytes_accessed = b
    except Exception:  # noqa: BLE001 - cost analysis is optional evidence
        pass
    try:
        text = lowered.as_text()
        targets = set(_TARGET_RE.findall(text))
        targets.update(_STABLEHLO_CC_RE.findall(text))
        entry.custom_calls = len(_CC_RE.findall(text))
        entry.custom_call_targets = tuple(sorted(targets))
        low = text.lower()
        entry.pallas = any(
            any(m in t.lower() for m in _PALLAS_MARKERS)
            for t in targets) or "tpu_custom_call" in low \
            or "__pallas" in low
        entry.audited = True
    except Exception:  # noqa: BLE001 - audit is optional evidence
        pass


# ---------------------------------------------------------------- readout

def _peak() -> Optional[float]:
    """Peak FLOP/s of the chip the programs run on.  None off-TPU: MFU
    is a chip metric, absent on a CPU rather than computed against a
    made-up peak.  A TPU kind missing from the table raises."""
    d = jax.devices()[0]
    if d.platform != "tpu":
        return None
    return _flops.peak_flops(d.device_kind)


def ledger() -> List[Dict[str, Any]]:
    """Per-program rows sorted by extrapolated device seconds (programs
    without samples sort last, by dispatch count)."""
    with _lock:
        entries = list(_entries.values())
        rows = []
        for e in entries:
            mean = (e.sampled_seconds / e.samples) if e.samples else None
            est = mean * e.dispatches if mean is not None else None
            rows.append({
                "program": e.key,
                "dispatches": e.dispatches,
                "samples": e.samples,
                "sampled_device_s": round(e.sampled_seconds, 6),
                "mean_sample_ms": (round(mean * 1e3, 4)
                                   if mean is not None else None),
                "est_device_s": (round(est, 6)
                                 if est is not None else None),
                "flops_per_dispatch": e.flops,
                "bytes_per_dispatch": e.bytes_accessed,
                "pallas": e.pallas,
                "_mean": mean, "_est": est, "_flops": e.flops})
    peak = _peak()
    total = sum(r["_est"] for r in rows if r["_est"]) or 0.0
    for r in rows:
        mean, est, f = r.pop("_mean"), r.pop("_est"), r.pop("_flops")
        achieved = (f / mean) if (f and mean) else None
        r["achieved_gflops_per_s"] = (round(achieved / 1e9, 3)
                                      if achieved else None)
        r["mfu"] = (round(achieved / peak, 6)
                    if achieved and peak is not None else None)
        r["device_time_frac"] = (round(est / total, 4)
                                 if est and total > 0 else None)
    rows.sort(key=lambda r: (-(r["est_device_s"] or 0.0),
                             -r["dispatches"], r["program"]))
    return rows


# serving-path labels for the audit table (key prefixes)
_PATHS = (
    ("serving.spec_tick", "spec verify chunk"),
    ("serving.prefill_cont", "suffix/chunked prefill"),
    ("serving.prefill", "monolithic prefill"),
    ("serving.tick", "decode tick"),
    ("serving.decode", "host-sampling decode"),
    ("serving.cow", "copy-on-write block copy"),
    ("optimizer.fused_step", "fused optimizer step"),
    ("moe.dispatch", "moe dispatch/combine"),
)
# ROADMAP item 5b names these as the paths suspected of running the
# dense gather/scatter instead of the paged/flash/MoE Pallas kernels
_KERNEL_SUSPECTS = ("serving.prefill_cont", "serving.spec_tick",
                    "moe.dispatch")


def _path_label(name: str) -> str:
    for prefix, label in _PATHS:
        if name == prefix or name.startswith(prefix):
            return label
    return name


def kernel_coverage() -> List[Dict[str, Any]]:
    """The kernel-coverage audit: one row per AUDITED program
    (attach_lowered saw its lowered text), reporting whether the hot
    path runs a Pallas kernel and via which evidence channel —
    ``"custom_call"`` (the HLO scan found the Mosaic call; the TPU
    case) or ``"interpret"`` (a trace-time claim; interpret-mode
    pallas_call leaves no HLO marker).  The ROADMAP 5b suspects (suffix
    prefill, spec verify, MoE dispatch) carry an explicit dense-gather
    note when NEITHER channel reports a kernel — evidence, not
    inference."""
    with _lock:
        entries = [e for e in _entries.values() if e.audited]
    rows = []
    for e in sorted(entries, key=lambda e: e.key):
        claimed = e.kernel_claims
        kernel = e.pallas or bool(claimed)
        if e.pallas:
            via = "custom_call"
        elif claimed:
            # all claims in one program share the lowering mode
            via = claimed[0][1]
        else:
            via = None
        row = {"program": e.key,
               "path": _path_label(e.name),
               "pallas": e.pallas,
               "kernel": kernel,
               "via": via,
               "kernels": sorted({n for n, _ in claimed}),
               "claims": [list(c) for c in claimed],
               "custom_calls": e.custom_calls,
               "targets": list(e.custom_call_targets)}
        if not kernel and any(e.name == s or e.name.startswith(s)
                              for s in _KERNEL_SUSPECTS):
            row["note"] = ("dense gather — no Pallas custom call in "
                           "the lowered HLO and no trace-time kernel "
                           "claim on this build (ROADMAP 5b suspect)")
        rows.append(row)
    return rows


def report(top: Optional[int] = None) -> Dict[str, Any]:
    """The full X-ray document: the ledger (optionally truncated to the
    ``top`` programs by device time) + the kernel-coverage table."""
    rows = ledger()
    total = sum(r["est_device_s"] for r in rows
                if r["est_device_s"]) or 0.0
    return {"schema": "paddle_tpu.xray/v1",
            "sample_interval": _SAMPLE_INTERVAL,
            "device_kind": jax.devices()[0].device_kind,
            "peak_flops_per_chip": _peak(),
            "total_est_device_s": round(total, 6),
            "programs_tracked": len(rows),
            "programs": rows[:top] if top else rows,
            "kernel_coverage": kernel_coverage()}


def reset() -> None:
    """Drop every entry (tests / per-rung bench isolation).  The
    registry counters are owned by the metrics registry and reset with
    it."""
    with _lock:
        _entries.clear()


_init_from_flag()
