"""Persistent XLA compilation cache — the cold-start killer.

Compiling is most of a cold run: minutes of XLA work against
millisecond steps, paid again by every restart (autoscaling,
preemption, deploys, and each sealed machine a chip run gets) to rebuild
byte-identical executables.  jax ships the fix —
``jax_compilation_cache_dir`` persists compiled executables keyed by
(HLO, compile options, jax/XLA version, accelerator).  This module is
the ONE seat that points it somewhere, by one rule
(:func:`resolve_dir`):

1. ``JAX_COMPILATION_CACHE_DIR`` in the environment: that directory, as
   given.  Whoever launches the process places the cache (a chip run
   that is handed a cache which survives the call sets exactly this),
   and nothing in the repo writes another directory over it — not a
   flag, not ``bench.py``, not ``incubate.autotune``.
2. else ``FLAGS_compilation_cache_dir``, when set.
3. else ``<checkout>/.jax_cache`` (gitignored) — a FIXED path, never a
   temp name, pid or time: a directory that moves never hits.

:func:`configure` turns the cache on at that directory
(``FLAGS_enable_compilation_cache=0`` is the one thing that turns it
off) and applies the entry floors
(``FLAGS_compilation_cache_min_entry_bytes``,
``FLAGS_compilation_cache_min_compile_secs``).
:func:`initialize_from_flags` calls it at package import — before any
backend touch — when the environment variable or the flag names a
directory; programs that want the cache regardless (``chip_smoke.py``,
``bench.py``, ``incubate.autotune``) call :func:`configure` themselves
and land on rule 3.  The flag ``on_change`` hooks re-apply at runtime.

Cache effectiveness is *observable* whichever rule chose the directory:
jax's monitoring events feed the ``compile.cache_hits_total`` /
``compile.cache_misses_total`` registry counters (rendered by the
Prometheus exporter under exactly those names) and :func:`cache_report`
— hits, misses, hit ratio, on-disk entries/bytes, retrieval seconds —
which ``observability.compile_tracker.compile_report()`` embeds so one
``--compile-report`` readout answers both "who compiled" and "did the
persistent cache absorb it".

Cache keying (what makes an entry reusable): the key hashes the
optimized HLO module, the compile options (donation, device assignment),
and the jax/jaxlib + PJRT platform versions.  Same program + same
toolchain + same accelerator ⇒ warm restarts skip XLA entirely; any of
those changing ⇒ a clean miss, never a stale executable.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional

import jax
from jax._src import compilation_cache as _jax_cc
from jax._src import monitoring as _jax_monitoring

from .. import flags as _flags
from ..observability import metrics as _metrics

__all__ = [
    "configure", "initialize_from_flags", "cache_report", "active_dir",
    "is_enabled", "resolve_dir", "ENV_VAR", "DEFAULT_DIR",
]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: this file is <checkout>/paddle_tpu/core/...
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_M_HITS = _metrics.counter(
    "compile.cache_hits_total", "persistent compilation-cache hits: an "
    "XLA compile request served from the cache directory instead of "
    "compiling (the warm-restart fast path)")
_M_MISSES = _metrics.counter(
    "compile.cache_misses_total", "persistent compilation-cache misses: "
    "compile requests that ran XLA and (when above the entry-size/"
    "compile-time floors) wrote a new cache entry")

# jax monitoring event names
_EV_HIT = "/jax/compilation_cache/cache_hits"
_EV_MISS = "/jax/compilation_cache/cache_misses"
_EV_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_EV_SAVED = "/jax/compilation_cache/compile_time_saved_sec"

_lock = threading.RLock()
_state: Dict[str, Any] = {
    "dir": None,           # the directory actually applied to jax
    "listeners": False,    # monitoring listeners installed once
    "hits": 0, "misses": 0,
    "retrieval_s": 0.0,    # wall seconds spent reading cache entries
    "saved_s": 0.0,        # jax's estimate of compile seconds avoided
}


# ----------------------------------------------------------- monitoring

def _on_event(event: str, **kwargs) -> None:
    if event == _EV_HIT:
        with _lock:
            _state["hits"] += 1
        _M_HITS.inc()
    elif event == _EV_MISS:
        with _lock:
            _state["misses"] += 1
        _M_MISSES.inc()


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == _EV_RETRIEVAL:
        with _lock:
            _state["retrieval_s"] += float(duration)
    elif event == _EV_SAVED:
        # jax reports (estimated compile time - retrieval time); it can
        # go slightly negative for tiny programs — keep the honest sum
        with _lock:
            _state["saved_s"] += float(duration)


def _install_listeners() -> None:
    """Register the jax monitoring listeners exactly once (they are
    process-global; double registration would double-count)."""
    with _lock:
        if _state["listeners"]:
            return
        _jax_monitoring.register_event_listener(_on_event)
        _jax_monitoring.register_event_duration_secs_listener(_on_duration)
        _state["listeners"] = True


# ---------------------------------------------------------- application

def resolve_dir() -> str:
    """The cache directory by the module's one rule: the environment
    variable (verbatim), else the flag, else ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_VAR, "")
    if env:
        return env
    flag = str(_flags.get_flag("compilation_cache_dir"))
    if flag:
        return os.path.abspath(os.path.expanduser(flag))
    return DEFAULT_DIR


def configure() -> Optional[str]:
    """Turn the persistent cache on at :func:`resolve_dir`'s directory,
    with the entry floors the flags give; returns the active cache
    directory (None = disabled by ``FLAGS_enable_compilation_cache=0``).

    There are deliberately no arguments: no call site chooses where the
    cache lives or what it keeps.  Idempotent, and safe to call before
    OR after backend init: ``jax.config`` updates are plain config state
    and the cache is consulted per compile request.
    """
    # flag reads happen OUTSIDE _lock: flags.set_flags holds the flags
    # lock while its on_change hook enters configure(), so taking the
    # locks here in the opposite order would be an AB-BA deadlock
    enable = bool(_flags.get_flag("enable_compilation_cache"))
    directory = resolve_dir() if enable else None
    if directory:
        os.makedirs(directory, exist_ok=True)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(_flags.get_flag("compilation_cache_min_compile_secs")))
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes",
            int(_flags.get_flag("compilation_cache_min_entry_bytes")))
    _apply_dir(directory)
    if directory:
        _install_listeners()
    return directory


def _apply_dir(directory: Optional[str]) -> None:
    """The one place ``jax_compilation_cache_dir`` is written."""
    with _lock:
        jax.config.update("jax_compilation_cache_dir", directory)
        # jax LATCHES cache-in-use at the first compile of the process
        # (and pins the cache object to the dir it initialized with):
        # without a reset, enabling after anything compiled is silently
        # ignored, and disabling keeps feeding a stale dir.  Return it
        # to pristine so the next compile re-reads the config we just
        # wrote.
        _jax_cc.reset_cache()
        _state["dir"] = directory


def initialize_from_flags() -> Optional[str]:
    """One-shot apply at package import (the "backend init" seat: it
    runs before the first program can possibly compile).  Acts when the
    environment variable or ``FLAGS_compilation_cache_dir`` names a
    directory — the variable alone already points jax at it; this adds
    the floors, the hit/miss listeners and the :func:`cache_report`
    bookkeeping.  With neither set it is a no-op: a library user who
    never asked for a persistent cache does not get one."""
    if not os.environ.get(ENV_VAR) \
            and not str(_flags.get_flag("compilation_cache_dir")):
        return None
    return configure()


def flags_changed(_value=None) -> None:
    """on_change hook for every compilation_cache_* flag: re-apply.
    Only acts once a directory is in play, so merely flipping the
    min-size flags pre-enable stays a no-op.  Emptying the flag that
    chose the directory detaches it; a directory chosen by the
    environment variable or by an explicit :func:`configure` (the
    checkout default) stays."""
    applied = active_dir()
    if os.environ.get(ENV_VAR) \
            or str(_flags.get_flag("compilation_cache_dir")) \
            or applied == DEFAULT_DIR:
        configure()
    elif applied:
        _apply_dir(None)


# -------------------------------------------------------------- readout

def active_dir() -> Optional[str]:
    """The cache directory currently applied to jax (None = disabled)."""
    with _lock:
        return _state["dir"]


def is_enabled() -> bool:
    return active_dir() is not None


def cache_report() -> Dict[str, Any]:
    """Cache effectiveness, process-local counters + on-disk totals:
    ``{enabled, dir, hits, misses, hit_ratio, entries, bytes,
    retrieval_seconds, compile_seconds_saved}``.  Embedded in
    ``compile_tracker.compile_report()`` and the ``--compile-report``
    CLI so hit ratio reads next to the compile ledger it explains."""
    with _lock:
        d = _state["dir"]
        hits, misses = _state["hits"], _state["misses"]
        retrieval_s, saved_s = _state["retrieval_s"], _state["saved_s"]
    entries = 0
    total_bytes = 0
    if d and os.path.isdir(d):
        try:
            for fname in os.listdir(d):
                path = os.path.join(d, fname)
                try:
                    size = os.path.getsize(path)
                except OSError:
                    continue
                total_bytes += size
                if not fname.endswith("-atime"):  # jax's access stamps
                    entries += 1
        except OSError:
            pass
    requests = hits + misses
    return {
        "enabled": d is not None,
        "dir": d,
        "hits": hits,
        "misses": misses,
        "hit_ratio": round(hits / requests, 4) if requests else None,
        "entries": entries,
        "bytes": total_bytes,
        "retrieval_seconds": round(retrieval_s, 4),
        "compile_seconds_saved": round(saved_s, 4),
    }
