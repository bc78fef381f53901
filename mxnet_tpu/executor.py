"""Graph executor: bind a Symbol, compile to one XLA program, run fwd/bwd.

Reference counterpart: ``src/executor/graph_executor.cc`` (1,866 LoC of
NNVM pass orchestration: Gradient, PlaceDevice, PlanMemory, AttachOpExecs,
memory pooling, cached engine ops, bulking — SURVEY §2.2/§3.1). TPU-native
design: the whole of that machinery is replaced by tracing the graph into
jitted JAX functions — XLA performs memory planning, fusion, scheduling and
(through jax.vjp) the gradient pass. Three compiled artifacts per executor:

- ``fwd_infer``  : inference forward (is_train=False)
- ``fwd_train``  : training forward (batch stats, dropout active)
- ``fwd_bwd``    : fused forward+backward → (outputs, grads, aux updates) —
  the Module training hot path, one XLA module per step (the analogue of
  the reference's bulked op segments, graph_executor.cc:1502).

Model parallelism note (ISSUE 20): ``bind(group2ctx=...)`` below is the
LEGACY per-op device-placement style (ctx_group attributes → explicit
devices, the reference's PlaceDevice pass). The TPU-native path shards
tensors instead: a ``(dp, mp)`` mesh (``parallel/mesh.py:train_mesh``)
with megatron column/row ``PartitionSpec`` rules applied by
``parallel/spmd.py:param_shardings`` — GSPMD then partitions this same
traced program across the mesh. Prefer ``MXNET_MP_SIZE`` over group2ctx
for anything larger than a two-device demo.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as _np

from . import profiler as _profiler
from .base import MXNetError, dtype_name, dtype_np
from .context import Context, current_context
from .ndarray.ndarray import NDArray, array as nd_array, zeros as nd_zeros
from .symbol.symbol import _AUX_PARAMS, Symbol

_RNG_SALT = 0x5EED


def eval_node(node, ins, key, salt, is_train):
    """Evaluate ONE symbol-graph node under the executor's op-invocation
    contract: ``__is_train__`` threading for train/infer-polymorphic ops
    and the per-node RNG fold-in for stochastic ops. Always returns a
    tuple of outputs. Shared by the training/inference closures below
    and the serving tier's constant-fold / inference split
    (``mxnet_tpu/serving/predictor.py``) so both bind paths invoke ops
    identically."""
    attrs = dict(node.attrs)
    if "__is_train__" in node.op.attr_defaults:
        attrs["__is_train__"] = is_train
    if node.op.needs_rng:
        sub = jax.random.fold_in(key, salt + _RNG_SALT)
        out = node.op.fn(sub, *ins, **attrs)
    else:
        out = node.op.fn(*ins, **attrs)
    return out if isinstance(out, tuple) else (out,)


def _graph_closure(symbol: Symbol, is_train: bool, placement=None):
    """Build a pure function evaluating the symbol graph.

    Returns fn(values: dict[str, jax.Array], key) -> (outputs, aux_updates)
    where aux_updates maps aux var name -> new value (BatchNorm moving
    stats etc., applied by the caller after forward).

    ``placement`` maps a ``ctx_group`` name to a concrete jax.Device: the
    TPU-native PlaceDevice pass (ref: graph_executor.cc:411). Each node
    stamped with that group is pinned there via ``jax.device_put`` inside
    the traced program; XLA inserts the cross-device transfers that the
    reference realized as explicit ``_CrossDeviceCopy`` nodes, in both the
    forward and (through the transpose of device_put) the gradient graph.
    """
    nodes = symbol._topo()
    entries = symbol._entries
    node_ids = {id(n): i for i, n in enumerate(nodes)}
    placement = placement or {}

    def _place(node, out):
        dev = placement.get(node.attr_dict.get("ctx_group"))
        if dev is None:
            return out
        return tuple(jax.device_put(o, dev) for o in out)

    def fn(values, key):
        results = {}  # node id -> tuple of outputs
        aux_updates = {}
        for i, node in enumerate(nodes):
            if node.is_variable():
                if node.name not in values:
                    raise MXNetError("unbound variable %r" % node.name)
                results[i] = _place(node, (values[node.name],))
                continue
            ins = [results[node_ids[id(inp)]][idx] for inp, idx in node.inputs]
            out = _place(node, eval_node(node, ins, key, i, is_train))
            results[i] = out
            # generic aux-state contract: op declares which outputs
            # replace which aux inputs each training step (fused blocks)
            if is_train and node.op.aux_state_outputs and node._arity:
                for pname, (inode, _) in zip(node._arity, node.inputs):
                    idx = node.op.aux_state_outputs.get(pname)
                    if idx is not None and inode.is_variable():
                        aux_updates[inode.name] = out[idx]
            # aux-state update semantics (BatchNorm moving stats)
            elif is_train and node.op.name in _AUX_PARAMS and node._arity:
                momentum = node.attrs.get("momentum", 0.9)
                for pname, (inode, _) in zip(node._arity, node.inputs):
                    if not inode.is_variable():
                        continue
                    if pname == "moving_mean":
                        aux_updates[inode.name] = (
                            momentum * values[inode.name] + (1 - momentum) * out[1]
                        )
                    elif pname == "moving_var":
                        aux_updates[inode.name] = (
                            momentum * values[inode.name] + (1 - momentum) * out[2]
                        )
        outs = [results[node_ids[id(n)]][idx] for n, idx in entries]
        return outs, aux_updates

    return fn


# ---------------------------------------------------------------------------
# shape/type inference (ref: src/executor/infer_graph_attr_pass.cc — here a
# single jax.eval_shape abstract evaluation replaces the fixpoint pass)
# ---------------------------------------------------------------------------
def infer_graph_shapes(symbol, kwargs, partial=False, type_dict=None):
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    known = {}
    for k, v in kwargs.items():
        if v is not None:
            known[k] = tuple(v)
    shapes, dtypes = _solve_shapes(symbol, known, type_dict or {}, partial=partial)
    if shapes is None:
        return None, None, None
    arg_shapes = [shapes.get(n) for n in arg_names]
    aux_shapes = [shapes.get(n) for n in aux_names]
    out_shapes = shapes["__outputs__"]
    return arg_shapes, out_shapes, aux_shapes


def infer_graph_types(symbol, kwargs):
    """Propagate dtypes through the graph by abstract evaluation.

    Needs at least placeholder shapes: uses per-variable __shape__ attrs or
    rank-agnostic (1,1,1,1) fallbacks, since XLA dtype rules are shape-
    independent for the ops we register."""
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    dtypes = {k: v for k, v in kwargs.items() if v is not None}
    # dummy rank-1 shapes let elementwise/cast chains propagate dtype even
    # when real shapes are unknown; shape-constrained ops fall back to f32
    dummy = {n: (1,) for n in arg_names + aux_names}
    try:
        shapes, out_dtypes = _solve_shapes(symbol, dummy, dtypes, partial=True)
        out_types = out_dtypes if out_dtypes else [None] * len(symbol._entries)
    except Exception:
        out_types = [None] * len(symbol._entries)
    arg_types = [dtype_np(dtypes.get(n, _np.float32)) for n in arg_names]
    aux_types = [dtype_np(dtypes.get(n, _np.float32)) for n in aux_names]
    out_types = [t if t is not None else _np.float32 for t in out_types]
    return arg_types, out_types, aux_types


def _solve_shapes(symbol, known_shapes, type_dict, partial=False):
    """Infer all variable shapes by constraint propagation.

    Strategy (TPU-first; replaces NNVM's per-op FInferShape): per-op python
    shape rules for the parameterized layers (Convolution/FC/RNN/…) whose
    weights can't be deduced by abstract evaluation alone, then a final
    jax.eval_shape over the whole graph to fill outputs and validate.
    """
    nodes = symbol._topo()
    node_ids = {id(n): i for i, n in enumerate(nodes)}
    shapes = dict(known_shapes)  # varname -> shape
    dtypes = {k: dtype_np(v) for k, v in type_dict.items()}

    node_out = {}  # node idx -> list of (shape, dtype)
    node_errors = {}  # node name -> last abstract-eval error (diagnostics)

    def get_in_structs(node):
        ins = []
        for inp, idx in node.inputs:
            if inp.is_variable():
                s = shapes.get(inp.name)
                ins.append(None if s is None else (s, dtypes.get(inp.name, _np.float32)))
            else:
                outs = node_out.get(node_ids[id(inp)])
                ins.append(outs[idx] if outs else None)
        return ins

    progress = True
    rounds = 0
    while progress and rounds < len(nodes) + 2:
        progress = False
        rounds += 1
        for i, node in enumerate(nodes):
            if node.is_variable():
                if node.name not in shapes and "__shape__" in node.attr_dict:
                    sh = node.attr_dict["__shape__"]
                    if isinstance(sh, str):
                        from .ops.registry import _parse_tuple

                        sh = _parse_tuple(sh)
                    shapes[node.name] = tuple(sh)
                    progress = True
                if node.name not in dtypes and "__dtype__" in node.attr_dict:
                    dtypes[node.name] = dtype_np(node.attr_dict["__dtype__"])
                continue
            if i in node_out:
                continue
            in_structs = get_in_structs(node)
            hints = _param_shape_hints(node, [s[0] if s else None for s in in_structs])
            if hints:
                for pname, shape in hints.items():
                    for an, (inode, _) in zip(node._arity or (), node.inputs):
                        if an != pname:
                            continue
                        # the int8 serving path routes weights through
                        # an in-graph _quantize_rows_int8 node (shape-
                        # preserving on output 0): the hint lands on
                        # the variable BEHIND it
                        if (not inode.is_variable()
                                and inode.op.name == "_quantize_rows_int8"
                                and inode.inputs
                                and inode.inputs[0][0].is_variable()):
                            inode = inode.inputs[0][0]
                        if inode.is_variable() and inode.name not in shapes:
                            shapes[inode.name] = shape
                            in_structs = get_in_structs(node)
                            progress = True
            if any(s is None for s in in_structs):
                continue
            # abstract eval this node: shapes AND dtypes in one pass
            attrs = dict(node.attrs)
            if "__is_train__" in node.op.attr_defaults:
                attrs["__is_train__"] = False
            try:
                structs = [jax.ShapeDtypeStruct(s, d) for s, d in in_structs]
                if node.op.needs_rng:
                    kstruct = jax.ShapeDtypeStruct((2,), _np.uint32)
                    out = jax.eval_shape(lambda k, *a: node.op.fn(k, *a, **attrs), kstruct, *structs)
                else:
                    out = jax.eval_shape(lambda *a: node.op.fn(*a, **attrs), *structs)
                out = out if isinstance(out, tuple) else (out,)
                node_out[i] = [(tuple(o.shape), o.dtype) for o in out]
                node_errors.pop(node.name, None)
                progress = True
            except Exception as e:
                # unresolved nodes are normal mid-fixpoint; keep the last
                # error per node so a *final* failure names its cause
                # (set MXNET_INFER_DEBUG=1 for full tracebacks)
                lines = str(e).strip().splitlines()
                node_errors[node.name] = "%s(%s): %s" % (
                    node.op.name, node.name,
                    lines[-1][:200] if lines else type(e).__name__)
                if os.environ.get("MXNET_INFER_DEBUG"):
                    import sys
                    import traceback

                    print("[infer_shape] node %r (%s) failed:\n%s"
                          % (node.name, node.op.name,
                             traceback.format_exc()), file=sys.stderr)
                continue

    out_shapes = []
    out_dtypes = []
    ok = True
    for n, idx in symbol._entries:
        if n.is_variable():
            out_shapes.append(shapes.get(n.name))
            out_dtypes.append(dtypes.get(n.name))
        else:
            outs = node_out.get(node_ids[id(n)])
            out_shapes.append(outs[idx][0] if outs else None)
            out_dtypes.append(outs[idx][1] if outs else None)
        if out_shapes[-1] is None:
            ok = False
    if not ok and not partial:
        missing = [v.name for v in nodes if v.is_variable() and v.name not in shapes]
        detail = "; ".join(list(node_errors.values())[:3])
        raise MXNetError(
            "infer_shape failed; unresolved variables: %s%s"
            % (missing, (" — node errors: " + detail) if detail else ""))
    shapes["__outputs__"] = out_shapes
    return shapes, out_dtypes


def _param_shape_hints(node, in_shapes):
    """Infer parameter shapes from data shape for parameterized layers
    (the NNVM FInferShape backward-direction rules the compiler can't do)."""
    op = node.op.name
    attrs = node.attrs
    data = in_shapes[0] if in_shapes else None
    if data is None:
        return {}
    hints = {}
    if op in ("Convolution", "Convolution_v1", "_ConvResidualAdd",
              "_int8_convolution"):
        # the IR rewrites (_ConvResidualAdd, the int8 serving conv)
        # keep Convolution's weight contract exactly
        kernel = tuple(int(k) for k in attrs.get("kernel", ()))
        nf = int(attrs.get("num_filter", 1))
        ng = int(attrs.get("num_group", 1))
        hints["weight"] = (nf, data[1] // ng) + kernel
        if not attrs.get("no_bias"):
            hints["bias"] = (nf,)
        if op == "_int8_convolution":
            hints["wscale"] = (nf,)
    elif op == "FusedBottleneckUnit":
        # data is NHWC; weights keep the unfused OIHW checkpoint shapes
        nf = int(attrs.get("num_filter", 1))
        c = nf // 4
        ci = data[3]
        hints["conv1_weight"] = (c, ci, 1, 1)
        hints["conv2_weight"] = (c, c, 3, 3)
        hints["conv3_weight"] = (nf, c, 1, 1)
        hints["sc_weight"] = (nf, ci, 1, 1)
        for i, ch in (("1", ci), ("2", c), ("3", c)):
            hints["bn%s_gamma" % i] = (ch,)
            hints["bn%s_beta" % i] = (ch,)
            hints["bn%s_moving_mean" % i] = (ch,)
            hints["bn%s_moving_var" % i] = (ch,)
    elif op == "Deconvolution":
        kernel = tuple(int(k) for k in attrs.get("kernel", ()))
        nf = int(attrs.get("num_filter", 1))
        ng = int(attrs.get("num_group", 1))
        hints["weight"] = (data[1], nf // ng) + kernel
        if not attrs.get("no_bias", True):
            hints["bias"] = (nf,)
    elif op in ("FullyConnected", "_int8_fully_connected"):
        nh = int(attrs.get("num_hidden", 1))
        flatten = attrs.get("flatten", True)
        in_dim = 1
        if flatten:
            for d in data[1:]:
                in_dim *= d
        else:
            in_dim = data[-1]
        hints["weight"] = (nh, in_dim)
        if not attrs.get("no_bias"):
            hints["bias"] = (nh,)
        if op == "_int8_fully_connected":
            hints["wscale"] = (nh,)
    elif op in ("BatchNorm", "BatchNorm_v1", "batch_norm"):
        ax = int(attrs.get("axis", 1)) % len(data)
        c = data[ax]
        hints.update({"gamma": (c,), "beta": (c,), "moving_mean": (c,), "moving_var": (c,)})
    elif op == "LayerNorm":
        ax = int(attrs.get("axis", -1)) % len(data)
        c = data[ax]
        hints.update({"gamma": (c,), "beta": (c,)})
    elif op == "InstanceNorm":
        hints.update({"gamma": (data[1],), "beta": (data[1],)})
    elif op == "Embedding":
        hints["weight"] = (int(attrs.get("input_dim", 0)), int(attrs.get("output_dim", 0)))
    elif op == "LeakyReLU" and attrs.get("act_type") == "prelu":
        hints["gamma"] = (data[1] if len(data) > 1 else 1,)
    elif op in ("SoftmaxOutput", "SVMOutput"):
        # label shape deduced from data (ref: SoftmaxOutput FInferShape) so
        # inference-only binds need no label_shapes
        if op == "SoftmaxOutput" and attrs.get("multi_output"):
            hints["label"] = (data[0],) + tuple(data[2:])
        else:
            hints["label"] = (data[0],)
    elif op in ("LinearRegressionOutput", "MAERegressionOutput",
                "LogisticRegressionOutput"):
        hints["label"] = tuple(data)
    elif op == "RNN":
        H = int(attrs.get("state_size", 0))
        L = int(attrs.get("num_layers", 1))
        D = 2 if attrs.get("bidirectional") else 1
        mode = attrs.get("mode", "lstm")
        ngates = {"lstm": 4, "gru": 3, "rnn_relu": 1, "rnn_tanh": 1}[mode]
        I = data[2]
        size = 0
        for layer in range(L):
            for d in range(D):
                in_size = I if layer == 0 else H * D
                size += ngates * H * in_size + ngates * H * H
        size += L * D * 2 * ngates * H
        hints["parameters"] = (size,)
        hints["state"] = (L * D, data[1], H)
        if mode == "lstm":
            hints["state_cell"] = (L * D, data[1], H)
    return hints


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------
class Executor:
    """A bound computation (ref: include/mxnet/executor.h Executor)."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None):
        self._symbol = symbol
        self._ctx = Context(ctx) if not isinstance(ctx, Context) else ctx
        self._monitor_callback = None
        self._group2ctx = group2ctx

        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()

        # normalize args
        if isinstance(args, dict):
            self.arg_dict = dict(args)
            missing = [n for n in arg_names if n not in self.arg_dict]
            if missing:
                raise MXNetError("bind: missing arguments %s" % missing)
        else:
            if len(args) != len(arg_names):
                raise MXNetError(
                    "bind: expected %d args, got %d" % (len(arg_names), len(args))
                )
            self.arg_dict = dict(zip(arg_names, args))
        self.arg_arrays = [self.arg_dict[n] for n in arg_names]

        if aux_states is None:
            aux_states = {}
        if isinstance(aux_states, dict):
            self.aux_dict = dict(aux_states)
        else:
            self.aux_dict = dict(zip(aux_names, aux_states))
        for n in aux_names:
            if n not in self.aux_dict:
                raise MXNetError("bind: missing auxiliary state %r" % n)
        self.aux_arrays = [self.aux_dict[n] for n in aux_names]

        # grad requirements
        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(arg_names, grad_req))
        else:
            self.grad_req = {n: grad_req.get(n, "null") for n in arg_names}

        if args_grad is None:
            self.grad_dict = {}
        elif isinstance(args_grad, dict):
            self.grad_dict = dict(args_grad)
        else:
            self.grad_dict = dict(zip(arg_names, args_grad))
        self.grad_arrays = [self.grad_dict.get(n) for n in arg_names]

        self._arg_names = arg_names
        self._aux_names = aux_names
        self._grad_names = [
            n for n in arg_names if self.grad_req.get(n, "null") != "null" and self.grad_dict.get(n) is not None
        ]

        self.outputs = []
        self._out_arrays = None
        self._compiled = {}
        self._rng_counter = 0
        self._last_fwd_train = False

    # -- compilation ---------------------------------------------------------
    def _placement(self):
        """ctx_group name → jax.Device map from the bind-time group2ctx
        (ref: symbol.py:1255 group2ctx → PlaceDevice)."""
        if not self._group2ctx:
            return None
        return {g: (Context(c) if not isinstance(c, Context) else c).jax_device()
                for g, c in self._group2ctx.items()}

    def _get_compiled(self, kind):
        fn = self._compiled.get(kind)
        if fn is not None:
            return fn
        placement = self._placement()
        if kind in ("fwd_infer", "fwd_train"):
            is_train = kind == "fwd_train"
            graph = _graph_closure(self._symbol, is_train, placement)

            def run(values, key):
                outs, aux_updates = graph(values, key)
                return outs, aux_updates

            fn = jax.jit(run)
        elif kind == "fwd_bwd":
            graph = _graph_closure(self._symbol, True, placement)
            grad_names = tuple(self._grad_names)
            # MXNET_BACKWARD_DO_MIRROR: recompute-in-backward (sublinear
            # memory; ref graph_executor.cc:282-305 mirror predicate →
            # jax.checkpoint on the whole bound program)
            from . import config as _cfg

            if _cfg.get_bool("MXNET_BACKWARD_DO_MIRROR"):
                graph = jax.checkpoint(graph)

            def run(values, key, head_grads):
                def of_grads(gvals):
                    all_vals = dict(values)
                    all_vals.update(gvals)
                    outs, aux_updates = graph(all_vals, key)
                    return outs, aux_updates

                gvals = {n: values[n] for n in grad_names}
                outs, vjp_fn = jax.vjp(lambda gv: of_grads(gv)[0], gvals)
                # aux updates from a plain re-eval (free under jit — XLA CSE)
                _, aux_updates = of_grads(gvals)
                cts = [
                    hg if hg is not None else jnp.ones_like(o)
                    for hg, o in zip(head_grads, outs)
                ]
                (grads,) = vjp_fn(cts)
                return outs, grads, aux_updates

            fn = jax.jit(run)
        else:
            raise MXNetError(kind)
        self._compiled[kind] = fn
        return fn

    def _values(self, include_aux=True):
        vals = {n: self.arg_dict[n]._data() for n in self._arg_names}
        if include_aux:
            for n in self._aux_names:
                vals[n] = self.aux_dict[n]._data()
        return vals

    def _next_key(self):
        from . import random as _rnd

        return _rnd.next_key(self._ctx)

    # -- execution -----------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k in self.arg_dict:
                tgt = self.arg_dict[k]
                src = v if isinstance(v, NDArray) else nd_array(v, ctx=self._ctx)
                tgt._rebind(src._data().astype(tgt._data().dtype) if src._data().dtype != tgt._data().dtype else src._data())
        fn = self._get_compiled("fwd_train" if is_train else "fwd_infer")
        key = self._next_key()
        self._last_key = key  # backward() must replay the same PRNG draws
        # ref: executor RunOps stamps each push (graph_executor.cc:1461);
        # one XLA program = one event here
        with _profiler.span("mx.executor.forward"):
            outs, aux_updates = fn(self._values(), key)
        self._last_fwd_train = is_train
        self._set_outputs(outs)
        self._aux_applied = False
        if is_train:
            self._apply_aux(aux_updates)
            self._aux_applied = True
        if self._monitor_callback is not None:
            for name, val in zip(self._symbol.list_outputs(), self.outputs):
                self._monitor_callback(name, val)
        return self.outputs

    def _set_outputs(self, outs):
        if self._out_arrays is None:
            self._out_arrays = [NDArray(o, ctx=self._ctx) for o in outs]
        else:
            for arr, o in zip(self._out_arrays, outs):
                arr._rebind(o)
        self.outputs = self._out_arrays

    def _apply_aux(self, aux_updates):
        for name, val in aux_updates.items():
            self.aux_dict[name]._rebind(val)

    def backward(self, out_grads=None, is_train=True):
        """Backward pass. Runs the fused fwd+bwd XLA program (forward results
        are recomputed inside the compiled module — XLA CSE makes the fused
        program the fast path; see class docstring)."""
        heads = self._normalize_head_grads(out_grads)
        fn = self._get_compiled("fwd_bwd")
        with _profiler.span("mx.executor.backward"):
            outs, grads, aux_updates = fn(self._values(), self._reuse_key(), heads)
        self._set_outputs(outs)
        if not getattr(self, "_aux_applied", False):
            self._apply_aux(aux_updates)
        self._aux_applied = False
        for n in self._grad_names:
            buf = self.grad_dict.get(n)
            if buf is None:
                continue
            g = grads[n]
            if self.grad_req.get(n) == "add":
                buf._rebind(buf._data() + g)
            else:
                buf._rebind(g)
        return grads

    def forward_backward(self, out_grads=None, **kwargs):
        """Fused training step — forward + backward in one compiled call."""
        for k, v in kwargs.items():
            if k in self.arg_dict:
                tgt = self.arg_dict[k]
                src = v if isinstance(v, NDArray) else nd_array(v, ctx=self._ctx)
                tgt._rebind(src._data())
        heads = self._normalize_head_grads(out_grads)
        fn = self._get_compiled("fwd_bwd")
        key = self._next_key()
        self._last_key = key
        with _profiler.span("mx.executor.forward_backward"):
            outs, grads, aux_updates = fn(self._values(), key, heads)
        self._set_outputs(outs)
        self._apply_aux(aux_updates)
        self._aux_applied = False
        for n in self._grad_names:
            buf = self.grad_dict.get(n)
            if buf is None:
                continue
            if self.grad_req.get(n) == "add":
                buf._rebind(buf._data() + grads[n])
            else:
                buf._rebind(grads[n])
        return self.outputs

    def _reuse_key(self):
        key = getattr(self, "_last_key", None)
        if key is None:
            key = self._next_key()
        return key

    def _normalize_head_grads(self, out_grads):
        n_out = len(self._symbol._entries)
        if out_grads is None:
            return [None] * n_out
        if isinstance(out_grads, NDArray):
            out_grads = [out_grads]
        return [g._data() if isinstance(g, NDArray) else g for g in out_grads] + [None] * (
            n_out - len(out_grads)
        )

    # -- parameter management ------------------------------------------------
    def copy_params_from(self, arg_params, aux_params=None, allow_extra_params=False):
        for name, arr in (arg_params or {}).items():
            if name in self.arg_dict:
                arr.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise MXNetError("unknown argument %r" % name)
        for name, arr in (aux_params or {}).items():
            if name in self.aux_dict:
                arr.copyto(self.aux_dict[name])
            elif not allow_extra_params:
                raise MXNetError("unknown aux state %r" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = {}
        for name, shape in zip(self._arg_names, arg_shapes):
            old = self.arg_dict[name]
            if tuple(old.shape) == tuple(shape):
                new_args[name] = old
            else:
                new_args[name] = nd_zeros(shape, ctx=self._ctx, dtype=old.dtype)
        new_grads = {}
        for name in self._arg_names:
            g = self.grad_dict.get(name)
            if g is None:
                continue
            shape = new_args[name].shape
            new_grads[name] = g if tuple(g.shape) == tuple(shape) else nd_zeros(shape, ctx=self._ctx, dtype=g.dtype)
        new_aux = {}
        for name, shape in zip(self._aux_names, aux_shapes):
            old = self.aux_dict[name]
            new_aux[name] = old if tuple(old.shape) == tuple(shape) else nd_zeros(shape, ctx=self._ctx, dtype=old.dtype)
        return Executor(self._symbol, self._ctx, new_args, new_grads, self.grad_req, new_aux)

    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor_callback = callback

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def debug_str(self):
        return self._symbol.debug_str()


def simple_bind(symbol, ctx, grad_req="write", type_dict=None, shared_exec=None,
                group2ctx=None, **kwargs):
    """Allocate arg/grad/aux arrays from inferred shapes and bind
    (ref: symbol.py:1255-1512 simple_bind + memory sharing via shared_exec —
    memory pooling is XLA's job here, so shared_exec only shares buffers)."""
    arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**{
        k: v for k, v in kwargs.items() if isinstance(v, (list, tuple))
    })
    if arg_shapes is None:
        raise MXNetError("simple_bind: shape inference failed")
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    type_dict = type_dict or {}
    args = {}
    for name, shape in zip(arg_names, arg_shapes):
        dtype = type_dict.get(name, _np.float32)
        if shared_exec is not None and name in shared_exec.arg_dict and tuple(shared_exec.arg_dict[name].shape) == tuple(shape):
            args[name] = shared_exec.arg_dict[name]
        else:
            args[name] = nd_zeros(shape, ctx=ctx, dtype=dtype)
    grad_req_dict = (
        {n: grad_req for n in arg_names} if isinstance(grad_req, str) else dict(grad_req)
    )
    grads = {}
    for name in arg_names:
        if grad_req_dict.get(name, "null") != "null":
            if shared_exec is not None and name in shared_exec.grad_dict and shared_exec.grad_dict[name] is not None and tuple(shared_exec.grad_dict[name].shape) == tuple(args[name].shape):
                grads[name] = shared_exec.grad_dict[name]
            else:
                grads[name] = nd_zeros(args[name].shape, ctx=ctx, dtype=type_dict.get(name, _np.float32))
    aux = {}
    for name, shape in zip(aux_names, aux_shapes):
        if shared_exec is not None and name in shared_exec.aux_dict and tuple(shared_exec.aux_dict[name].shape) == tuple(shape):
            aux[name] = shared_exec.aux_dict[name]
        else:
            aux[name] = nd_zeros(shape, ctx=ctx, dtype=type_dict.get(name, _np.float32))
    return Executor(symbol, ctx, args, grads, grad_req_dict, aux,
                    group2ctx=group2ctx)
