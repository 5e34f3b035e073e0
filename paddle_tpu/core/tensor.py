"""The user-facing Tensor: a Paddle-compatible facade over `jax.Array`.

Reference parity: `phi::DenseTensor` (`paddle/phi/core/dense_tensor.h:37`) +
the eager Tensor bound in pybind (`paddle/fluid/pybind/eager.cc`,
`eager_method.cc`) with its autograd meta (`eager/autograd_meta.h:61`) and the
Python-side method patches (`python/paddle/fluid/dygraph/math_op_patch.py`,
`varbase_patch_methods.py:206 backward`).

Storage is an on-device `jax.Array`; XLA owns device memory, so the
reference's allocator stack (`paddle/fluid/memory/`) maps to jax's PJRT
allocator + `device_put`.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtype_mod
from . import place as place_mod
from . import autograd


class Tensor:
    __array_priority__ = 100  # win over numpy in mixed expressions

    __slots__ = (
        "_data", "stop_gradient", "_grad", "_grad_node", "_out_slot",
        "name", "persistable", "_grad_hooks", "trainable", "dist_spec",
        "_layout",
    )

    def __init__(self, data, dtype=None, place=None, stop_gradient=True,
                 name=None):
        dt = dtype_mod.convert_dtype(dtype)
        if isinstance(data, Tensor):
            arr = data._data
            if dt is not None and arr.dtype != dt:
                arr = arr.astype(dt)
        elif isinstance(data, jax.Array):
            arr = data if dt is None or data.dtype == dt else data.astype(dt)
        else:
            if isinstance(data, (bool, int, float)) and dt is None:
                if isinstance(data, bool):
                    dt = dtype_mod.bool_
                elif isinstance(data, int):
                    dt = dtype_mod.convert_dtype("int64")
                else:
                    dt = dtype_mod.get_default_dtype()
            npa = np.asarray(data)
            if dt is None and npa.dtype == np.float64:
                dt = dtype_mod.get_default_dtype()
            arr = jnp.asarray(npa, dtype=dt)
        if place is not None and not isinstance(place, place_mod.Place):
            s = str(place).lower()
            place = (place_mod.CPUPlace(0) if s.startswith("cpu")
                     else place_mod.TPUPlace(0))
        if isinstance(place, place_mod.Place):
            arr = jax.device_put(arr, place.jax_device())
        self._data = arr
        # physical-layout tag (core/layout.py): None = logical layout;
        # "NHWC" = logically-NCHW image stored channels-last. Inherited
        # when wrapping another Tensor (same backing array).
        self._layout = data._layout if isinstance(data, Tensor) else None
        self.stop_gradient = bool(stop_gradient)
        self._grad = None
        self._grad_node = None
        self._out_slot = 0
        self.name = name
        self.persistable = False
        self.trainable = not stop_gradient
        self._grad_hooks = []
        self.dist_spec = None  # jax PartitionSpec for SPMD placement

    # -- basic metadata -------------------------------------------------
    @property
    def shape(self):
        if self._layout is not None:       # physical NHWC -> logical NCHW
            from . import layout as layout_mod
            s = self._data.shape
            return [s[i] for i in layout_mod.TO_NCHW_PERM]
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    def dim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(self._data.size)

    def numel(self):
        return int(self._data.size)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def place(self):
        dev = next(iter(self._data.devices()))
        cls = (place_mod.TPUPlace if dev.platform == "tpu"
               else place_mod.CPUPlace)
        return cls(dev.id)

    @property
    def is_leaf(self):
        return self._grad_node is None

    @property
    def grad(self):
        return self._grad

    @grad.setter
    def grad(self, value):
        if value is not None and not isinstance(value, Tensor):
            value = Tensor(value)
        self._grad = value

    @property
    def T(self):
        from .. import ops
        perm = list(range(self.ndim))[::-1]
        return ops.transpose(self, perm)

    def real(self, name=None):
        # a METHOD, matching the reference Tensor.real(name=None) —
        # property-style `.real` (torch-ism) would break ported calls
        from ..ops.extras2 import real as _real
        return _real(self)

    def imag(self, name=None):
        from ..ops.extras2 import imag as _imag
        return _imag(self)

    # -- host interop ---------------------------------------------------
    def numpy(self):
        a = np.asarray(self._data)
        if self._layout is not None:       # hand back the logical layout
            from . import layout as layout_mod
            a = a.transpose(*layout_mod.TO_NCHW_PERM)
        if a.base is not None or not a.flags.owndata:
            # Paddle's Tensor.numpy() returns a SNAPSHOT (a writable
            # copy), but np.asarray of a CPU jax buffer is a read-only
            # zero-copy VIEW of the live device buffer. Handing that
            # view out is a correctness trap with buffer donation: a
            # donated executable may reuse the buffer in place and
            # silently rewrite the caller's "snapshot". Fresh-compiled
            # executables dodge it (PJRT sees the external reference
            # and copies instead of donating), but executables
            # DESERIALIZED from the persistent compilation cache skip
            # that protection on this jax — observed as hapi-trained
            # weights "never changing" because the pre-training
            # snapshot aliased the donated param buffer. Copy-on-view
            # only: backends whose device_get already materializes an
            # owning host array pay nothing.
            a = a.copy()
        return a

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def element_size(self):
        return self._data.dtype.itemsize

    # -- autograd -------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        # run_backward converts logical-NCHW cotangents for tagged roots
        autograd.run_backward([self], [grad_tensor], retain_graph)

    def clear_grad(self):
        self._grad = None

    clear_gradient = clear_grad

    def register_hook(self, hook):
        self._grad_hooks.append(hook)

        class _Removable:
            def remove(_self):
                try:
                    self._grad_hooks.remove(hook)
                except ValueError:
                    pass
        return _Removable()

    def detach(self):
        t = Tensor(self._data, stop_gradient=True)
        t._layout = self._layout
        t.name = self.name
        return t

    def clone(self):
        from .. import ops
        return ops.assign(self)

    def set_value(self, value):
        """In-place data rebind (paddle Tensor.set_value)."""
        if isinstance(value, Tensor):
            arr = value._data
        else:
            arr = jnp.asarray(np.asarray(value))
        if arr.dtype != self._data.dtype:
            arr = arr.astype(self._data.dtype)
        if tuple(arr.shape) != tuple(self._data.shape):
            raise ValueError(
                f"set_value shape mismatch {arr.shape} vs {self._data.shape}"
            )
        self._data = arr

    def copy_(self, other, blocking=True):
        self.set_value(other)
        return self

    # -- conversion / movement -----------------------------------------
    def astype(self, dt):
        from .. import ops
        return ops.cast(self, dt)

    def cast(self, dt):
        return self.astype(dt)

    def to(self, *args, **kwargs):
        t = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, (str, place_mod.Place)):
                dev = a if isinstance(a, place_mod.Place) else None
                if dev is None:
                    s = str(a)
                    dev = (place_mod.CPUPlace(0) if s.startswith("cpu")
                           else place_mod.TPUPlace(0))
                out = Tensor(jax.device_put(t._data, dev.jax_device()),
                             stop_gradient=t.stop_gradient)
                out._grad_node, out._out_slot = t._grad_node, t._out_slot
                out._layout = t._layout
                t = out
            else:
                t = t.astype(a)
        return t

    def cpu(self):
        return self.to("cpu")

    def cuda(self, *a, **k):
        return self.to("tpu")

    def tpu(self):
        return self.to("tpu")

    def pin_memory(self):
        return self

    # -- python protocol ------------------------------------------------
    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self.shape[0]

    def __bool__(self):
        if self.size != 1:
            raise ValueError(
                "The truth value of a multi-element Tensor is ambiguous"
            )
        return bool(self.numpy().reshape(()))

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __index__(self):
        return int(self.item())

    def __repr__(self):
        return (
            f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
            f"place={self.place}, stop_gradient={self.stop_gradient},\n"
            f"       {np.array2string(self.numpy(), prefix='       ')})"
        )

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __hash__(self):
        return id(self)

    # -- indexing -------------------------------------------------------
    def __getitem__(self, idx):
        from .. import ops
        return ops.getitem(self, idx)

    def __setitem__(self, idx, value):
        from .. import ops
        out = ops.setitem(self, idx, value)
        # Paddle mutates in place; we rebind this wrapper to the new value
        # (version-counter semantics: downstream autograd uses the new node).
        self._data = out._data
        self._grad_node = out._grad_node
        self._out_slot = out._out_slot
        self.stop_gradient = out.stop_gradient
        self._layout = out._layout  # setitem materialized a tagged self


def _make_binop(opname, reverse=False):
    def fn(self, other):
        from .. import ops
        f = getattr(ops, opname)
        if reverse:
            return f(other, self)
        return f(self, other)
    return fn


for _name, _op in [
    ("__add__", "add"), ("__sub__", "subtract"), ("__mul__", "multiply"),
    ("__truediv__", "divide"), ("__floordiv__", "floor_divide"),
    ("__mod__", "remainder"), ("__pow__", "pow"), ("__matmul__", "matmul"),
    ("__eq__", "equal"), ("__ne__", "not_equal"), ("__lt__", "less_than"),
    ("__le__", "less_equal"), ("__gt__", "greater_than"),
    ("__ge__", "greater_equal"), ("__and__", "bitwise_and"),
    ("__or__", "bitwise_or"), ("__xor__", "bitwise_xor"),
]:
    setattr(Tensor, _name, _make_binop(_op))

for _name, _op in [
    ("__radd__", "add"), ("__rsub__", "subtract"), ("__rmul__", "multiply"),
    ("__rtruediv__", "divide"), ("__rpow__", "pow"),
    ("__rmatmul__", "matmul"),
]:
    setattr(Tensor, _name, _make_binop(_op, reverse=True))


def _neg(self):
    from .. import ops
    return ops.scale(self, -1.0)


def _invert(self):
    from .. import ops
    return ops.logical_not(self)


Tensor.__neg__ = _neg
Tensor.__invert__ = _invert


class Parameter(Tensor):
    """Trainable tensor — `framework::Parameter`
    (`python/paddle/fluid/framework.py:6893`) parity."""

    __slots__ = ("optimize_attr", "regularizer", "do_model_average",
                 "need_clip", "is_distributed")

    def __init__(self, data, dtype=None, name=None, trainable=True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable,
                         name=name)
        self.trainable = trainable
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.do_model_average = None
        self.need_clip = True
        self.is_distributed = False

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()
