"""Random state management.

The reference keeps per-device stateful generators (`paddle.seed`,
`phi/core/generator.h`).  On TPU/XLA randomness must be functional, so the
global "generator" is a JAX PRNG key that is split on every draw.  Under jit
capture (paddle_tpu.jit) a *traced* key source is installed so random ops
(dropout, rand) become pure functions of a key argument threaded by the
captured program — the TPU-native equivalent of Paddle's RNG state tracker
(`fleet/layers/mpu/random.py` uses the same fold-in idea for TP determinism).
"""

from __future__ import annotations

import contextlib
import threading
import warnings

import jax
import numpy as np

__all__ = ["seed", "get_rng_state", "set_rng_state", "next_key",
           "key_source_guard", "rng_checkpoint_state",
           "restore_rng_checkpoint_state"]


def _key_impl():
    """PRNG implementation for framework keys.

    On TPU the default threefry bit generator is compute-heavy enough to
    show up in training steps dominated by dropout masks (the reference
    pays a fused curand path instead, `phi/kernels/funcs/dropout_impl.cu.h`);
    'rbg' generates bits an order of magnitude faster on the VPU and stays
    deterministic per backend.  FLAGS_tpu_fast_rng=0 restores threefry
    everywhere (bit-exact cross-backend streams)."""
    from .. import flags as _flags
    try:
        fast = _flags.get_flag("tpu_fast_rng")
    except Exception:  # flag registry not initialized yet
        fast = True
    if fast and jax.default_backend() == "tpu":
        return "rbg"
    return "threefry2x32"


def _host_cpu():
    try:
        # local_devices, not devices: in a multi-process job global CPU
        # device 0 belongs to process 0 and is not addressable elsewhere
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        # JAX_PLATFORMS names the accelerator alone, so no CPU backend is
        # registered.  The chain still works on the default device, but
        # every draw becomes a device launch — say so once, loudly.
        warnings.warn(
            "paddle_tpu RNG: no CPU backend is registered (JAX_PLATFORMS="
            "excludes 'cpu'); the PRNG key chain lives on "
            f"{jax.default_backend()} and every draw launches a device "
            "program.  Add 'cpu' to JAX_PLATFORMS to keep it on the host.",
            RuntimeWarning, stacklevel=3)
        return None


class StatefulKeySource:
    """Host-side stateful source: splits a stored key each draw.

    The key chain lives on the host CPU backend: a key living on the
    accelerator turns every draw into an extra device program launch that
    serializes with the real step's launch.  Splitting on host is free
    and the 32-byte subkey rides along with the step's arguments.  Both
    the chain and the subkeys handed out are UNCOMMITTED arrays (made
    under ``jax.default_device``, never ``device_put``): a consumer jit
    moves them to wherever its other arguments live — one chip or a mesh
    over four — where a key committed to device 0 would be refused as
    "incompatible devices" next to mesh-sharded arguments."""

    def __init__(self, seed_val: int = 0):
        # LAZY: touching a device here would initialize the XLA backend at
        # `import paddle_tpu` time, which breaks jax.distributed.initialize
        # (it must run before any backend use — init_parallel_env's seat)
        self._seed_val = seed_val
        self._cpu = None
        self._key = None
        self._lock = threading.Lock()

    def _on_host(self):
        """Context under which the chain's keys are made: the host CPU
        as default device (results uncommitted), or nothing when no CPU
        backend exists (`_host_cpu` has warned)."""
        if self._cpu is None:
            return contextlib.nullcontext()
        return jax.default_device(self._cpu)

    def _ensure(self):
        if self._key is not None:
            return
        self._cpu = _host_cpu()
        with self._on_host():
            self._key = jax.random.key(self._seed_val, impl=_key_impl())

    def next_key(self):
        with self._lock:
            self._ensure()
            with self._on_host():
                self._key, sub = jax.random.split(self._key)
            return sub

    def get_state(self):
        with self._lock:
            self._ensure()
        return self._key

    def set_state(self, key):
        """Adopt `key` as the chain head.  It is re-made from its raw
        bits on the host, so a key that arrives committed to a device (a
        restored checkpoint, another source's state) cannot commit the
        chain — and through it every later subkey — to that device."""
        with self._lock:
            self._ensure()
            data = np.asarray(jax.random.key_data(key))
            with self._on_host():
                self._key = jax.random.wrap_key_data(
                    data, impl=jax.random.key_impl(key))


class TracedKeySource:
    """Pure source used during jit capture: splits a traced key.

    The split counter is Python-side, so a fixed trace draws a deterministic
    *sequence* of subkeys from the per-call key argument — each call of the
    compiled function passes a fresh key, so randomness varies across steps.
    """

    def __init__(self, key):
        self._key = key

    def next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub


_state = threading.local()
_global_source = StatefulKeySource(0)


def _current_source():
    stack = getattr(_state, "stack", None)
    if stack:
        return stack[-1]
    return _global_source


def next_key():
    """Draw a fresh PRNG key from the active source (global or traced)."""
    return _current_source().next_key()


def seed(value: int):
    """Reset the global generator, like paddle.seed.

    Also seeds the global numpy RNG: the DataLoader samplers
    (``io.RandomSampler`` / ``io.WeightedRandomSampler``) draw their
    shuffle permutations from it, and the hapi resume machinery
    snapshots/restores that same global state for bit-identical
    mid-epoch continuation — so ``paddle.seed`` must pin it or batch
    order (and anything gated on it, like marginal accuracy
    assertions) differs between otherwise identical processes."""
    global _global_source
    _global_source = StatefulKeySource(int(value))
    np.random.seed(int(value) & 0xFFFFFFFF)
    return _global_source


def get_rng_state():
    return _global_source.get_state()


def set_rng_state(key):
    _global_source.set_state(key)


def rng_checkpoint_state():
    """Host-serializable snapshot of the global key chain: the raw key
    bits plus the PRNG impl name, so a restore re-wraps the exact key the
    crashed process would have split next (bit-identical streams)."""
    key = get_rng_state()
    return {"key_data": np.asarray(jax.random.key_data(key)),
            "impl": str(jax.random.key_impl(key))}


def restore_rng_checkpoint_state(state):
    """Inverse of `rng_checkpoint_state` (accepts its dict)."""
    import jax.numpy as jnp
    data = jnp.asarray(state["key_data"])
    set_rng_state(jax.random.wrap_key_data(data, impl=str(state["impl"])))


@contextlib.contextmanager
def key_source_guard(source):
    stack = getattr(_state, "stack", None)
    if stack is None:
        stack = _state.stack = []
    stack.append(source)
    try:
        yield source
    finally:
        stack.pop()
