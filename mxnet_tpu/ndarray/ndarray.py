"""NDArray — the imperative tensor value type, plus the op-invoke machinery.

Reference counterpart: ``include/mxnet/ndarray.h:79-921`` +
``python/mxnet/ndarray/ndarray.py``. TPU-native design: an NDArray is a
mutable *handle* over an immutable ``jax.Array``. The reference's
Chunk{Storage::Handle, Engine::Var} pair collapses to the jax buffer itself:
XLA's async dispatch provides the ThreadedEngine's read/write ordering, and
``WaitToRead`` becomes ``block_until_ready``. In-place ops rebind the
handle; views (slices) write through to their parent via lazy index update
(the copy-on-write discipline SURVEY §7 'hard parts' calls for).
"""
from __future__ import annotations

import numpy as _np

from .. import autograd as _ag
from .. import profiler as _prof
from .. import random as _random
from ..base import MXNetError, dtype_name, dtype_np
from ..context import Context, cpu, current_context
from ..ops import registry as _reg

__all__ = [
    "NDArray",
    "array",
    "empty",
    "zeros",
    "ones",
    "full",
    "arange",
    "concatenate",
    "moveaxis",
    "onehot_encode",
    "imdecode",
    "waitall",
    "invoke",
]


def _is_tensor_like(v):
    return isinstance(v, (NDArray, _np.ndarray)) or type(v).__module__.startswith("jax")


class NDArray:
    """Multi-dimensional array on a device context."""

    __slots__ = ("_jax", "_ctx", "_grad_entry", "_base", "_index", "_stype",
                 "_view_cache", "__weakref__")

    # numpy should defer binary ops to us
    __array_priority__ = 100.0

    def __init__(self, data, ctx=None, base=None, index=None, stype="default"):
        self._jax = data  # jax.Array | None (when view)
        self._ctx = ctx or current_context()
        self._grad_entry = None
        self._base = base  # parent NDArray when this is a view
        self._index = index  # index into parent
        self._stype = stype
        self._view_cache = None  # (base buffer, sliced value) memo

    # -- raw value access ----------------------------------------------------
    def _data(self):
        if self._base is not None:
            # memoize the computed slice per base buffer: every property
            # read (shape/dtype) goes through _data(), and zero-copy
            # iterator batches (NDArrayIter fast path) are views read
            # many times per batch — without the memo each read would
            # dispatch a fresh slice op
            base = self._base._data()
            cached = self._view_cache
            if cached is not None and cached[0] is base:
                return cached[1]
            value = base[self._index]
            self._view_cache = (base, value)
            return value
        return self._jax

    def _view_source(self):
        """``(value, index)`` with ``value[index]`` this array's contents
        (``value`` alone when ``index`` is None): a view's root buffer and
        its index into it, no slice dispatched, else what ``_data()``
        gives. ``DeviceQueueIter`` copies a host view's rows from there
        into its staging buffer without realizing the view."""
        if self._base is not None:
            return self._base._data(), self._index
        return self._data(), None

    def _rebind(self, new_value):
        """Point this handle at a new device buffer (in-place op semantics).

        If this array is a view, write through to the parent (the reference's
        shared-Chunk behavior, ndarray.h:635-875).
        """
        if self._base is not None:
            self._base._rebind(self._base._data().at[self._index].set(new_value))
        else:
            self._jax = new_value

    # -- basic properties ----------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data().shape)

    @property
    def ndim(self):
        return self._data().ndim

    @property
    def size(self):
        return int(self._data().size)

    @property
    def dtype(self):
        d = self._data().dtype
        return d.type if hasattr(d, "type") else d

    @property
    def stype(self):
        return self._stype

    @property
    def context(self):
        return self._ctx

    @property
    def ctx(self):
        return self._ctx

    @property
    def grad(self):
        e = self._grad_entry
        return e.grad if e is not None else None

    @property
    def handle(self):
        return self  # parity shim: some code passes .handle around

    # -- sync points (ref: NDArray::WaitToRead / Engine::WaitForAll) ---------
    def wait_to_read(self):
        self._data().block_until_ready()

    def wait_to_write(self):
        self._data().block_until_ready()

    def asnumpy(self):
        return _np.asarray(self._data())

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __bool__(self):
        if self.size != 1:
            raise ValueError("ambiguous truth value of multi-element NDArray")
        return bool(self.asscalar())

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __repr__(self):
        return "\n%s\n<%s %s @%s>" % (
            _np.asarray(self._data()),
            type(self).__name__,
            "x".join(str(s) for s in self.shape),
            self._ctx,
        )

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    # -- dtype / context movement --------------------------------------------
    def astype(self, dtype, copy=True):
        if dtype_name(self.dtype) == dtype_name(dtype) and not copy:
            return self
        return invoke("Cast", [self], {"dtype": dtype_name(dtype_np(dtype))})

    def copy(self):
        return invoke("_copy", [self], {})

    def copyto(self, other):
        """Copy into another NDArray or to a context (ref: CopyFromTo)."""
        import jax

        if isinstance(other, Context):
            arr = jax.device_put(self._data(), Context(other).jax_device())
            return NDArray(arr, ctx=Context(other))
        if isinstance(other, NDArray):
            val = jax.device_put(self._data(), other._ctx.jax_device())
            if val.dtype != other._data().dtype:
                val = val.astype(other._data().dtype)
            other._rebind(val.reshape(other.shape))
            return other
        raise MXNetError("copyto: unsupported target %r" % (other,))

    def as_in_context(self, context):
        if context == self._ctx:
            return self
        return self.copyto(context)

    def detach(self):
        out = NDArray(self._data(), ctx=self._ctx)
        return out

    def attach_grad(self, grad_req="write", stype=None):
        """Allocate grad buffer & mark as autograd variable (gluon surface)."""
        grad = zeros(self.shape, ctx=self._ctx, dtype=self.dtype)
        _ag.mark_variables([self], [grad], grad_reqs=grad_req)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        _ag.backward([self], [out_grad] if out_grad is not None else None,
                     retain_graph=retain_graph, train_mode=train_mode)

    # -- indexing ------------------------------------------------------------
    def __getitem__(self, key):
        key = self._norm_key(key)
        if isinstance(key, NDArray):
            return invoke("take", [self, key], {"axis": 0, "mode": "clip"})
        if self._base is not None:
            composed = self._chain_index(key)
            if composed is None:
                # the key has no single-root-index form (tuple/fancy
                # keys, or a view over one): read out of the
                # materialized view instead — writes to the result do
                # not flow back to the root, same as take() copies
                return NDArray(self._data()[key], ctx=self._ctx)
            return NDArray(None, ctx=self._ctx, base=self._root(),
                           index=composed)
        # return a view that writes through on _rebind
        return NDArray(None, ctx=self._ctx, base=self._root(), index=key)

    def _root(self):
        return self._base if self._base is not None else self

    def _chain_index(self, key):
        """Compose a key applied to this view into one root index, or
        None when the composition has no single-index form (tuple and
        fancy keys). Slice-of-slice (any step/sign) and integer keys
        stay zero-copy write-through views — the batch-feed path
        slices iterator views again per device
        (executor_group._load_slice on NDArrayIter's zero-copy batches)
        and must not force a copy, and a detached copy would silently
        break the write-through contract single-level views have."""
        idx = self._index
        if not isinstance(idx, slice):
            return None  # view over an int/fancy key: row has no axis 0
        rows = range(*idx.indices(self._base._data().shape[0]))
        if isinstance(key, (int, _np.integer)) and not isinstance(key, bool):
            return rows[int(key)]  # IndexError out of range, as numpy
        if isinstance(key, slice):
            r = rows[key]
            # a negative normalized stop only happens stepping downward
            # past row 0, where the sentinel is None
            return slice(r.start, r.stop if r.stop >= 0 else None, r.step)
        return None

    def _norm_key(self, key):
        if isinstance(key, NDArray) and key.dtype != _np.bool_:
            return key
        if isinstance(key, _np.ndarray):
            return array(key, ctx=self._ctx)
        return key

    def __setitem__(self, key, value):
        import jax.numpy as jnp

        data = self._data()
        if isinstance(key, slice) and key.start is None and key.stop is None and key.step is None:
            # a[:] = v  — full overwrite
            self._rebind(self._coerce_value(value, data.shape, data.dtype))
            return
        if isinstance(key, NDArray):
            key = key._data()
        val = value._data() if isinstance(value, NDArray) else value
        if isinstance(val, (int, float)):
            self._rebind(data.at[key].set(val))
        else:
            val = jnp.asarray(val, dtype=data.dtype)
            self._rebind(data.at[key].set(val))

    def _coerce_value(self, value, shape, dtype):
        import jax.numpy as jnp

        if isinstance(value, NDArray):
            v = value._data()
        elif isinstance(value, (int, float)):
            return jnp.full(shape, value, dtype=dtype)
        else:
            v = jnp.asarray(value)
        v = v.astype(dtype) if v.dtype != dtype else v
        return jnp.broadcast_to(v, shape) if v.shape != tuple(shape) else v.reshape(shape)

    # -- shape ops (fluent methods, ref: ndarray.py fluent section) ----------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        if kwargs.get("shape"):
            shape = tuple(kwargs["shape"])
        return invoke("Reshape", [self], {"shape": shape, "reverse": kwargs.get("reverse", False)})

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def broadcast_to(self, shape):
        return invoke("broadcast_to", [self], {"shape": tuple(shape)})

    def broadcast_like(self, other):
        return invoke("broadcast_like", [self, other], {})

    @property
    def T(self):
        return invoke("transpose", [self], {})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return invoke("transpose", [self], {"axes": axes})

    def expand_dims(self, axis):
        return invoke("expand_dims", [self], {"axis": axis})

    def flatten(self):
        return invoke("Flatten", [self], {})

    def squeeze(self, axis=None):
        return invoke("squeeze", [self], {"axis": axis})

    def swapaxes(self, dim1, dim2):
        return invoke("swapaxes", [self], {"dim1": dim1, "dim2": dim2})

    def flip(self, axis):
        return invoke("flip", [self], {"axis": axis})

    def tile(self, reps):
        return invoke("tile", [self], {"reps": reps})

    def repeat(self, repeats, axis=None):
        return invoke("repeat", [self], {"repeats": repeats, "axis": axis})

    def pad(self, mode, pad_width, constant_value=0.0):
        return invoke("pad", [self], {"mode": mode, "pad_width": pad_width, "constant_value": constant_value})

    def slice(self, begin, end, step=()):
        return invoke("slice", [self], {"begin": begin, "end": end, "step": step})

    def slice_axis(self, axis, begin, end):
        return invoke("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return invoke("take", [self, indices], {"axis": axis, "mode": mode})

    def one_hot(self, depth, on_value=1.0, off_value=0.0, dtype="float32"):
        return invoke("one_hot", [self], {"depth": depth, "on_value": on_value, "off_value": off_value, "dtype": dtype})

    def pick(self, index, axis=-1, keepdims=False):
        return invoke("pick", [self, index], {"axis": axis, "keepdims": keepdims})

    # -- reductions ----------------------------------------------------------
    def sum(self, axis=None, keepdims=False):
        return invoke("sum", [self], {"axis": axis, "keepdims": keepdims})

    def nansum(self, axis=None, keepdims=False):
        return invoke("nansum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return invoke("mean", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False):
        return invoke("prod", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False):
        return invoke("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False):
        return invoke("min", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke("norm", [self], {"ord": ord, "axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False):
        return invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def argsort(self, axis=-1, is_ascend=True):
        return invoke("argsort", [self], {"axis": axis, "is_ascend": is_ascend})

    def sort(self, axis=-1, is_ascend=True):
        return invoke("sort", [self], {"axis": axis, "is_ascend": is_ascend})

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return invoke("topk", [self], {"axis": axis, "k": k, "ret_typ": ret_typ, "is_ascend": is_ascend})

    # -- elementwise fluent --------------------------------------------------
    def abs(self):
        return invoke("abs", [self], {})

    def sign(self):
        return invoke("sign", [self], {})

    def square(self):
        return invoke("square", [self], {})

    def sqrt(self):
        return invoke("sqrt", [self], {})

    def exp(self):
        return invoke("exp", [self], {})

    def log(self):
        return invoke("log", [self], {})

    def relu(self):
        return invoke("relu", [self], {})

    def sigmoid(self):
        return invoke("sigmoid", [self], {})

    def tanh(self):
        return invoke("tanh", [self], {})

    def softmax(self, axis=-1):
        return invoke("softmax", [self], {"axis": axis})

    def log_softmax(self, axis=-1):
        return invoke("log_softmax", [self], {"axis": axis})

    def clip(self, a_min, a_max):
        return invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def round(self):
        return invoke("round", [self], {})

    def rint(self):
        return invoke("rint", [self], {})

    def floor(self):
        return invoke("floor", [self], {})

    def ceil(self):
        return invoke("ceil", [self], {})

    def trunc(self):
        return invoke("trunc", [self], {})

    def dot(self, other, transpose_a=False, transpose_b=False):
        from . import sparse as _sp

        if isinstance(self, _sp.CSRNDArray) and not transpose_b:
            # sparse segment-sum kernel, not the dense fallback
            return _sp.dot(self, other, transpose_a=transpose_a)
        return invoke("dot", [self, other], {"transpose_a": transpose_a, "transpose_b": transpose_b})

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse as _sp

        return _sp.cast_storage(self, stype)

    def as_np_ndarray(self):
        return self.asnumpy()

    # -- arithmetic dunders --------------------------------------------------
    def _binop(self, other, op, scalar_op, reverse=False):
        if isinstance(other, NDArray):
            args = [other, self] if reverse else [self, other]
            return invoke(op, args, {})
        if isinstance(other, (int, float, _np.generic)):
            return invoke(scalar_op, [self], {"scalar": float(other)})
        if isinstance(other, _np.ndarray):
            o = array(other, ctx=self._ctx)
            args = [o, self] if reverse else [self, o]
            return invoke(op, args, {})
        return NotImplemented

    def __add__(self, other):
        return self._binop(other, "broadcast_add", "_plus_scalar")

    def __radd__(self, other):
        return self._binop(other, "broadcast_add", "_plus_scalar")

    def __sub__(self, other):
        return self._binop(other, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, other):
        return self._binop(other, "broadcast_sub", "_rminus_scalar", reverse=True)

    def __mul__(self, other):
        return self._binop(other, "broadcast_mul", "_mul_scalar")

    def __rmul__(self, other):
        return self._binop(other, "broadcast_mul", "_mul_scalar")

    def __truediv__(self, other):
        return self._binop(other, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, other):
        return self._binop(other, "broadcast_div", "_rdiv_scalar", reverse=True)

    def __div__(self, other):
        return self.__truediv__(other)

    def __mod__(self, other):
        return self._binop(other, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, other):
        return self._binop(other, "broadcast_mod", "_rmod_scalar", reverse=True)

    def __pow__(self, other):
        return self._binop(other, "broadcast_power", "_power_scalar")

    def __rpow__(self, other):
        return self._binop(other, "broadcast_power", "_rpower_scalar", reverse=True)

    def __neg__(self):
        return invoke("negative", [self], {})

    def __abs__(self):
        return invoke("abs", [self], {})

    def __eq__(self, other):
        if other is None:
            return False
        return self._binop(other, "broadcast_equal", "_equal_scalar")

    def __ne__(self, other):
        if other is None:
            return True
        return self._binop(other, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, other):
        return self._binop(other, "broadcast_greater", "_greater_scalar")

    def __ge__(self, other):
        return self._binop(other, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, other):
        return self._binop(other, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, other):
        return self._binop(other, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    def __iadd__(self, other):
        res = self.__add__(other)
        self._rebind(res._data())
        return self

    def __isub__(self, other):
        res = self.__sub__(other)
        self._rebind(res._data())
        return self

    def __imul__(self, other):
        res = self.__mul__(other)
        self._rebind(res._data())
        return self

    def __itruediv__(self, other):
        res = self.__truediv__(other)
        self._rebind(res._data())
        return self

    def __getstate__(self):
        return {"data": self.asnumpy(), "ctx": (self._ctx.device_type, self._ctx.device_id)}

    def __setstate__(self, state):
        import jax

        ctx = Context(state["ctx"][0], state["ctx"][1])
        self._jax = jax.device_put(state["data"], ctx.jax_device())
        self._ctx = ctx
        self._grad_entry = None
        self._base = None
        self._index = None
        self._stype = "default"
        self._view_cache = None


# ---------------------------------------------------------------------------
# op invocation (the MXImperativeInvoke analogue, ref c_api_ndarray.cc:117)
# ---------------------------------------------------------------------------
_STATEFUL_POST = {}


def register_stateful_post(op_name):
    def deco(fn):
        _STATEFUL_POST[op_name] = fn
        return fn

    return deco


_SYMBOL_CLS = None


def invoke(op, inputs, attrs, out=None, ctx=None):
    """Invoke a registered op on NDArrays.

    Pipeline (mirrors Imperative::Invoke, src/imperative/imperative.cc:37-110):
    coerce attrs → thread PRNG key if needed → apply kernel via XLA →
    wrap outputs → rebind mutated inputs → record on autograd tape.
    """
    if isinstance(op, str):
        op = _reg.get(op)
    inputs = [x for x in inputs]
    # symbolic tracing (HybridBlock.export): any Symbol input composes a
    # graph node instead of executing — the layer code is F-agnostic
    global _SYMBOL_CLS
    if _SYMBOL_CLS is None:
        from ..symbol.symbol import Symbol as _SYMBOL_CLS_  # noqa: N806

        _SYMBOL_CLS = _SYMBOL_CLS_
    _Sym = _SYMBOL_CLS

    if any(isinstance(x, _Sym) for x in inputs):
        from ..symbol.register import create_symbol

        bad = [x for x in inputs if x is not None and not isinstance(x, _Sym)]
        if bad:
            raise MXNetError(
                "op %s: cannot mix NDArray and Symbol inputs during "
                "symbolic tracing" % op.name)
        sattrs = {k: v for k, v in attrs.items() if v is not None}
        sattrs.pop("name", None)
        sattrs.pop("ctx", None)
        return create_symbol(op, inputs, sattrs)
    ctx = ctx or (inputs[0]._ctx if inputs else None) or current_context()

    attrs = {k: v for k, v in attrs.items() if v is not None or k in ("axis", "dtype")}
    attrs.pop("name", None)
    attrs.pop("ctx", None) if "ctx" not in op.attr_defaults else None
    parsed = op.parse_attrs(attrs)
    if "__is_train__" in op.attr_defaults:
        parsed["__is_train__"] = _ag.is_training()

    raw = [x._data() if isinstance(x, NDArray) else x for x in inputs]
    key = _random.next_key(ctx) if op.needs_rng else None
    arrays = ([key] + raw) if op.needs_rng else raw

    apply, operands = (_reg.apply_op_with_key, arrays) if op.needs_rng \
        else (_reg.apply_op, raw)
    if _prof.all_operators():
        with _prof.span("mx.nd.operator", op=op.name):
            results = apply(op, operands, parsed)
    else:
        results = apply(op, operands, parsed)
    if not isinstance(results, tuple):
        results = (results,)

    n_vis = op.n_visible_outputs(parsed)

    # mutated inputs: rebind handles (optimizer update ops)
    if op.mutate_inputs:
        for out_idx, in_idx in enumerate(op.mutate_inputs):
            if in_idx < len(inputs) and out_idx < len(results) and isinstance(inputs[in_idx], NDArray):
                if op.name != "BatchNorm":
                    inputs[in_idx]._rebind(results[out_idx])

    post = _STATEFUL_POST.get(op.name)
    if post is not None:
        post(inputs, results, parsed)

    outputs = [NDArray(r, ctx=ctx) for r in results[:n_vis]]

    if out is not None:
        outs = [out] if isinstance(out, NDArray) else list(out)
        for o, r in zip(outs, results[:n_vis]):
            o._rebind(r if r.dtype == o._data().dtype else r.astype(o._data().dtype))
        outputs = outs

    if _ag.is_recording() and not op.nondiff:
        _ag.record_op(op, parsed, inputs, outputs, raw, rng_key=key)

    return outputs[0] if n_vis == 1 else outputs


@register_stateful_post("BatchNorm")
def _bn_post(inputs, results, attrs):
    """Moving-stat update: moving = momentum*moving + (1-m)*batch
    (ref: src/operator/nn/batch_norm.cc aux-state mutation)."""
    if not attrs.get("__is_train__") or attrs.get("use_global_stats"):
        return
    momentum = attrs.get("momentum", 0.9)
    _, mean, var = results[:3]
    mm, mv = inputs[3], inputs[4]
    if isinstance(mm, NDArray):
        mm._rebind(momentum * mm._data() + (1 - momentum) * mean)
    if isinstance(mv, NDArray):
        mv._rebind(momentum * mv._data() + (1 - momentum) * var)


def _wrap_raw(raw, ctx=None):
    return NDArray(raw, ctx=ctx or current_context())


def _wrap_result(res, ctx=None):
    if isinstance(res, tuple):
        return [_wrap_raw(r, ctx) for r in res]
    return _wrap_raw(res, ctx)


# ---------------------------------------------------------------------------
# creation functions (ref: python/mxnet/ndarray/utils.py + ndarray.py)
# ---------------------------------------------------------------------------
def array(source_array, ctx=None, dtype=None):
    import jax

    ctx = ctx or current_context()
    was_ndarray = isinstance(source_array, (_np.ndarray, NDArray)) or (
        type(source_array).__module__.startswith("jax")
    )
    if isinstance(source_array, NDArray):
        source_array = source_array.asnumpy()
    np_arr = _np.asarray(source_array)
    if dtype is None:
        # parity: python lists default to float32; numpy arrays keep their
        # dtype (except float64 → float32, the framework default precision)
        if not was_ndarray or np_arr.dtype == _np.float64:
            dtype = _np.float32
        else:
            dtype = np_arr.dtype
    np_arr = np_arr.astype(dtype_np(dtype)) if dtype_name(np_arr.dtype) != dtype_name(dtype) else np_arr
    return NDArray(jax.device_put(np_arr, ctx.jax_device()), ctx=ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, stype=None, **kwargs):
    ctx = ctx or current_context()
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return invoke("_zeros", [], {"shape": shape, "dtype": dtype_name(dtype_np(dtype))}, ctx=ctx)


def ones(shape, ctx=None, dtype=None, **kwargs):
    ctx = ctx or current_context()
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return invoke("_ones", [], {"shape": shape, "dtype": dtype_name(dtype_np(dtype))}, ctx=ctx)


def full(shape, val, ctx=None, dtype=None, out=None):
    ctx = ctx or current_context()
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return invoke("_full", [], {"shape": shape, "value": val, "dtype": dtype_name(dtype_np(dtype))}, out=out, ctx=ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    ctx = ctx or current_context()
    return invoke(
        "_arange",
        [],
        {"start": start, "stop": stop, "step": step, "repeat": repeat, "dtype": dtype_name(dtype_np(dtype))},
        ctx=ctx,
    )


def concatenate(arrays, axis=0, always_copy=True):
    return invoke("Concat", list(arrays), {"dim": axis})


def moveaxis(tensor, source, destination):
    import jax.numpy as jnp

    return _wrap_raw(jnp.moveaxis(tensor._data(), source, destination), tensor._ctx)


def onehot_encode(indices, out):
    depth = out.shape[1]
    res = invoke("one_hot", [indices], {"depth": depth})
    out._rebind(res._data().astype(out._data().dtype))
    return out


def imdecode(str_img, clip_rect=(0, 0, 0, 0), out=None, index=0, channels=3, mean=None):
    raise MXNetError("imdecode: use mxnet_tpu.image instead")


def waitall():
    """Block until all async computation completes (ref: Engine::WaitForAll)."""
    import jax

    (jax.device_put(0.0) + 0).block_until_ready()


def load(fname):
    from .utils import load as _load

    return _load(fname)


def save(fname, data):
    from .utils import save as _save

    return _save(fname, data)
