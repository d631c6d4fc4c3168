"""Eager Tensor.

TPU-native analogue of the reference's eager ``paddle.Tensor``
(C++ `paddle/fluid/pybind/eager.cc` + `eager_method.cc`, phi DenseTensor
`paddle/phi/core/dense_tensor.h:37`, AutogradMeta
`paddle/fluid/eager/autograd_meta.h:61`).  The storage is a ``jax.Array``
(PJRT buffer) — or a JAX tracer during jit capture, which is what lets the
whole eager API be traced into one XLA program.

Paddle semantics preserved:
* ``stop_gradient`` defaults to True; ``Parameter`` defaults to False.
* ``.backward()`` runs the tape engine (framework/autograd_engine.py).
* ``.grad`` is itself a Tensor.
Operator overloads and most methods are monkey-patched from paddle_tpu.ops
(mirroring `python/paddle/base/dygraph/tensor_patch_methods.py`).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtypes as _dtypes
from . import autograd_engine as _engine
from .dygraph import is_grad_enabled

__all__ = ["Tensor", "Parameter", "to_tensor", "is_tensor"]


def _coerce_value(data, dtype=None, place=None):
    if isinstance(data, Tensor):
        val = data._value
    elif isinstance(data, (jax.Array,)) or hasattr(data, "aval"):
        # jax array or tracer
        val = data
    else:
        if dtype is None and isinstance(data, (list, tuple, int, float)):
            probe = np.asarray(data)
            if probe.dtype == np.float64:
                dtype = _dtypes.get_default_dtype()
            elif probe.dtype == np.int64:
                dtype = np.int64
        val = jnp.asarray(data, dtype=_dtypes.convert_dtype(dtype) if dtype else None)
        dtype = None  # already applied
    if dtype is not None:
        d = _dtypes.convert_dtype(dtype)
        if val.dtype != d:
            val = val.astype(d)
    if place is not None and isinstance(val, jax.Array):
        val = jax.device_put(val, place.jax_device)
    return val


class Tensor:
    __slots__ = ("_value", "stop_gradient", "_grad", "_grad_node", "_output_slot",
                 "_accum_node", "_leaf_hooks", "name", "persistable", "trainable",
                 "_dist_attr", "__weakref__")

    def __init__(self, data=None, dtype=None, place=None, stop_gradient: bool = True,
                 name: Optional[str] = None):
        self._value = _coerce_value(data, dtype, place) if data is not None else None
        self.stop_gradient = stop_gradient
        self._grad: Optional[Tensor] = None
        self._grad_node: Optional[_engine.GradNode] = None
        self._output_slot: int = 0
        self._accum_node: Optional[_engine.GradAccumulationNode] = None
        self._leaf_hooks: List[Callable] = []
        self.name = name or f"tensor_{id(self):x}"
        self.persistable = False
        self.trainable = not stop_gradient
        self._dist_attr = None  # set by paddle_tpu.distributed for DistTensor

    # -- classmethod wrap: build from raw value without conversion ------------
    @classmethod
    def _wrap(cls, value, stop_gradient: bool = True) -> "Tensor":
        t = cls.__new__(cls)
        t._value = value
        t.stop_gradient = stop_gradient
        t._grad = None
        t._grad_node = None
        t._output_slot = 0
        t._accum_node = None
        t._leaf_hooks = []
        t.name = f"tensor_{id(t):x}"
        t.persistable = False
        t.trainable = not stop_gradient
        t._dist_attr = None
        return t

    # ------------------------------------------------------------------ meta
    @property
    def shape(self) -> List[int]:
        return list(self._value.shape)

    @property
    def dtype(self):
        return self._value.dtype

    @property
    def ndim(self) -> int:
        return self._value.ndim

    ndimension = ndim

    @property
    def size(self) -> int:
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def place(self):
        from ..core import device as _device
        if isinstance(self._value, jax.Array) and not self._is_traced():
            try:
                d = list(self._value.devices())[0]
                return _device.Place(d.platform, d.id)
            except Exception:
                pass
        return _device.current_place()

    def _is_traced(self) -> bool:
        return not isinstance(self._value, jax.Array) or isinstance(
            self._value, jax.core.Tracer)

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    # -------------------------------------------------------------- autograd
    @property
    def grad(self) -> Optional["Tensor"]:
        return self._grad

    @grad.setter
    def grad(self, value):
        if value is None:
            self._grad = None
        elif isinstance(value, Tensor):
            self._grad = value
        else:
            self._grad = Tensor._wrap(jnp.asarray(value))

    def _accumulate_grad(self, raw_grad):
        for hook in self._leaf_hooks:
            res = hook(Tensor._wrap(raw_grad))
            if res is not None:
                raw_grad = res._value if isinstance(res, Tensor) else res
        if raw_grad.dtype != self._value.dtype and jnp.issubdtype(
                self._value.dtype, jnp.floating):
            raw_grad = raw_grad.astype(self._value.dtype)
        # distributed invariant: grad layout follows the parameter layout
        # (the reference stores grads with the param's dist_attr)
        from jax.sharding import NamedSharding
        if (isinstance(raw_grad, jax.Array)
                and not isinstance(raw_grad, jax.core.Tracer)
                and isinstance(getattr(self._value, "sharding", None),
                               NamedSharding)
                and raw_grad.sharding != self._value.sharding):
            raw_grad = jax.device_put(raw_grad, self._value.sharding)
        if self._grad is None:
            self._grad = Tensor._wrap(raw_grad)
        else:
            self._grad._value = self._grad._value + raw_grad

    def _get_accum_node(self) -> _engine.GradAccumulationNode:
        if self._accum_node is None:
            self._accum_node = _engine.GradAccumulationNode(self)
        return self._accum_node

    @jax.named_scope("backward")
    def backward(self, grad_tensor=None, retain_graph: bool = False):
        """Run the autograd engine from this tensor.

        Reference: ``Tensor.backward`` →  ``core.eager.run_backward``
        (`python/paddle/base/dygraph/tensor_patch_methods.py:250,:335`).
        """
        if self.stop_gradient and self._grad_node is None:
            raise RuntimeError(
                "Tensor.backward() on a tensor with stop_gradient=True and no "
                "grad graph.")
        if grad_tensor is None:
            seed = jnp.ones(self._value.shape, self._value.dtype)
        else:
            seed = grad_tensor._value if isinstance(grad_tensor, Tensor) \
                else jnp.asarray(grad_tensor)
        _engine.run_backward([self], [seed], retain_graph=retain_graph)

    def register_hook(self, hook: Callable) -> "RemovableHandle":
        """Hook fires when this tensor's grad is computed; may return new grad."""
        if self._grad_node is None:
            self._leaf_hooks.append(hook)
            return RemovableHandle(self._leaf_hooks, hook)
        wrapped = _wrap_node_hook(hook)
        hooks = self._grad_node.grad_hooks[self._output_slot]
        hooks.append(wrapped)
        return RemovableHandle(hooks, wrapped)

    def clear_grad(self):
        self._grad = None

    clear_gradient = clear_grad

    def detach(self) -> "Tensor":
        return Tensor._wrap(self._value, stop_gradient=True)

    def detach_(self) -> "Tensor":
        self._grad_node = None
        self.stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        from .. import ops
        return ops.assign(self)

    # ------------------------------------------------------------- host sync
    def numpy(self) -> np.ndarray:
        return np.asarray(self._value)

    def item(self):
        from ..jit import sot as _sot
        return _sot.intercept("item", self, lambda: self._value.item())

    def tolist(self):
        return np.asarray(self._value).tolist()

    def __array__(self, dtype=None):
        a = np.asarray(self._value)
        return a.astype(dtype) if dtype is not None else a

    # jax interop: lets jnp.* consume Tensors directly.
    def __jax_array__(self):
        return self._value

    # -------------------------------------------------------------- mutation
    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._value
        new = jnp.asarray(value)
        if tuple(new.shape) != tuple(self._value.shape):
            raise ValueError(
                f"set_value shape mismatch: {new.shape} vs {self._value.shape}")
        self._value = new.astype(self._value.dtype)
        return self

    def copy_(self, other, blocking: bool = True):
        return self.set_value(other)

    def _to_place(self, place) -> "Tensor":
        val = jax.device_put(self._value, place.jax_device)
        t = Tensor._wrap(val, stop_gradient=self.stop_gradient)
        return t

    def cpu(self):
        from ..core.device import CPUPlace
        return self._to_place(CPUPlace())

    def to(self, *args, **kwargs):
        from ..core.device import Place
        dtype = kwargs.pop("dtype", None)
        device = kwargs.pop("device", None)
        for a in args:
            if isinstance(a, str) and (":" in a or a in ("cpu", "tpu", "gpu")):
                device = a
            elif isinstance(a, Place):
                device = a
            else:
                dtype = a
        out = self
        if dtype is not None:
            from .. import ops
            out = ops.cast(out, dtype)
        if device is not None:
            if isinstance(device, str):
                kind, _, idx = device.partition(":")
                device = Place(kind, int(idx or 0))
            out = out._to_place(device)
        return out

    # ---------------------------------------------------------------- dunder
    def __repr__(self):
        sg = self.stop_gradient
        if self._is_traced():
            return (f"Tensor(shape={self.shape}, dtype={self.dtype}, "
                    f"stop_gradient={sg}, traced)")
        return (f"Tensor(shape={self.shape}, dtype={self.dtype}, "
                f"stop_gradient={sg},\n       {np.asarray(self._value)!r})")

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self.shape[0]

    def __bool__(self):
        # concretizations route through the SOT hook: under guarded
        # capture (jit/sot.py) a traced value burns the recorded branch
        # and emits a guard instead of raising ConcretizationTypeError
        from ..jit import sot as _sot
        return _sot.intercept("bool", self, lambda: bool(self._value))

    def __int__(self):
        from ..jit import sot as _sot
        return _sot.intercept("int", self, lambda: int(self._value))

    def __float__(self):
        from ..jit import sot as _sot
        return _sot.intercept("float", self, lambda: float(self._value))

    def __hash__(self):
        return id(self)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __dlpack__(self, *a, **k):
        return self._value.__dlpack__(*a, **k)

    def __deepcopy__(self, memo):
        """Copy value + flags; the autograd graph is never copied (matches
        paddle: deepcopy of a mid-graph tensor detaches)."""
        cls = type(self)
        val = self._value
        if isinstance(val, jax.Array) and not self._is_traced():
            # a real buffer copy: the copy must survive the original being
            # donated by a jitted optimizer step (and vice versa)
            val = jnp.array(val, copy=True)
        t = cls._wrap(val, stop_gradient=self.stop_gradient)
        t.name = self.name  # stable identity: optimizer state keys by name
        t.persistable = self.persistable
        t.trainable = self.trainable
        if isinstance(self, Parameter):
            t.optimize_attr = dict(self.optimize_attr)
            t.need_clip = self.need_clip
        memo[id(self)] = t
        return t

    # Arithmetic/indexing dunders are patched in paddle_tpu/ops/__init__.py.


class RemovableHandle:
    def __init__(self, hooks_list, entry):
        self._list = hooks_list
        self._entry = entry

    def remove(self):
        try:
            self._list.remove(self._entry)
        except ValueError:
            pass


def _wrap_node_hook(user_hook):
    def node_hook(raw_grad):
        if raw_grad is None:
            return None
        res = user_hook(Tensor._wrap(raw_grad))
        if res is None:
            return None
        return res._value if isinstance(res, Tensor) else res
    return node_hook


class Parameter(Tensor):
    """Trainable tensor: stop_gradient=False, persistable, optimizer-visible.

    Reference: `python/paddle/base/framework.py` EagerParamBase.
    """
    __slots__ = ("optimize_attr", "regularizer", "is_distributed",
                 "need_clip", "_asp_mask")

    _name_counter = 0

    def __init__(self, data=None, dtype=None, name=None, trainable: bool = True):
        if name is None:
            # deterministic creation-order name (reference EagerParamBase
            # auto-names via a global unique_name counter) so optimizer
            # checkpoints keyed by param name are stable across processes
            name = f"param_{Parameter._name_counter}"
            Parameter._name_counter += 1
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.is_distributed = False
        self.need_clip = True

    @classmethod
    def _wrap(cls, value, stop_gradient: bool = False):
        t = super()._wrap.__func__(cls, value, stop_gradient)
        t.name = f"param_{Parameter._name_counter}"
        Parameter._name_counter += 1
        t.persistable = True
        t.optimize_attr = {"learning_rate": 1.0}
        t.regularizer = None
        t.is_distributed = False
        t.need_clip = True
        return t


def to_tensor(data, dtype=None, place=None, stop_gradient: bool = True) -> Tensor:
    """paddle.to_tensor equivalent."""
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)


def is_tensor(x) -> bool:
    return isinstance(x, Tensor)
