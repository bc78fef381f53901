"""SPMD fused training step: loss + grad + optimizer update in ONE XLA program.

Reference counterpart: the hot path assembled from
``DataParallelExecutorGroup`` (python/mxnet/module/executor_group.py:128 —
batch split across devices), ``Comm::Reduce``/KVStore push-pull gradient
sync (src/kvstore/comm.h:56, kvstore_local.h), and the ``sgd_mom_update``
CUDA kernels (src/operator/optimizer_op.cc:39-286). TPU-native design: all
three stages fuse into a single ``jax.jit`` program over a
``jax.sharding.Mesh`` —

- batch arrays are sharded over the data axes (``dp``); XLA inserts the
  gradient all-reduce (psum over ICI) where the reference ran NCCL/ps-lite,
  and overlaps it with backprop via its latency-hiding scheduler (the
  reference's priority-queue overlap, model.py:126-137).
- parameters may be sharded over ``tp`` (tensor parallel) by regex rules —
  the generalization of the reference's `group2ctx` model parallelism.
- the optimizer update runs on the sharded gradients in the same program
  (no separate push/pull round trip); with weight-update sharding
  (`zero=True`) each dp-shard updates a slice of the weights and
  all-gathers — the ZeRO analogue of the reference's server-side optimizer
  (kvstore_dist_server.h set_optimizer).
- mixed precision: master weights fp32, compute in ``compute_dtype``
  (bfloat16 on the MXU) — the mp_sgd_* multi-precision pattern
  (src/operator/optimizer_op.cc mp_sgd_update) without a separate kernel.

This module is pure-functional (params/states are pytrees, not NDArrays):
it is the engine under ``kvstore='tpu'`` Module training, ``bench.py`` and
``__graft_entry__.py``.
"""
from __future__ import annotations

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as _np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError

__all__ = [
    "param_shardings", "data_sharding", "replicated", "make_train_step",
    "TrainStep", "functional_optimizer", "functional_from_optimizer",
    "cross_entropy_loss", "parse_rules", "ShardingRuleError",
]

# Primitives whose outputs the remat="conv" policy SAVES. The fused
# Pallas units trace as custom_vjp/jvp call primitives (on CPU
# reference too), and pallas_call is what a kernel lowers to when the
# custom-vjp wrapper is absent — without these, a fused ResNet under
# remat="conv" recomputes its most expensive kernels in backward, the
# exact ops the policy exists to save (ISSUE 19 bugfix).
_SAVEABLE_PRIMS = (
    "conv_general_dilated",
    "dot_general",
    "pallas_call",
    "custom_vjp_call",
    "custom_vjp_call_jaxpr",
    "custom_jvp_call",
    "custom_jvp_call_jaxpr",
)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------
def replicated(mesh):
    return NamedSharding(mesh, P())


def data_sharding(mesh, axes=("dp",), ndim=None):
    """Shard the leading (batch) dimension over the given mesh axes."""
    names = [a for a in axes if a in mesh.axis_names]
    spec = P(tuple(names)) if names else P()
    return NamedSharding(mesh, spec)


class ShardingRuleError(MXNetError):
    """A parameter-sharding rule matched but cannot apply: the spec
    names a mesh axis the mesh does not have, or a sharded dim is not
    divisible by the axis size. Raised instead of silently replicating
    (ISSUE 20) — a silently replicated layer would defeat the 1/mp
    per-chip memory claim while looking healthy."""


def param_shardings(params, mesh, rules=None):
    """Map param name -> NamedSharding via ordered (regex, PartitionSpec)
    rules; first match wins, default replicated.

    Example rules for megatron-style tensor parallelism::

        [(r".*ffn_up_weight",  P("mp", None)),   # (out, in): shard out dim
         (r".*ffn_down_weight", P(None, "mp")),
         (r".*", P())]

    A matched rule that cannot apply — the spec names an axis the mesh
    does not have, or the sharded dim is not divisible by the axis
    size — raises :class:`ShardingRuleError` naming the parameter and
    the rule.
    """
    rules = rules or []
    out = {}
    for name, v in params.items():
        spec = P()
        rule_pat = None
        for pat, s in rules:
            if re.match(pat, name):
                spec = s if isinstance(s, P) else P(*s)
                rule_pat = pat
                break
        if spec != P():
            problem = _spec_misfit(spec, v.shape, mesh)
            if problem is not None:
                raise ShardingRuleError(
                    "param_shardings: rule (%r, %s) matched parameter "
                    "%r with shape %s but cannot apply: %s"
                    % (rule_pat, spec, name, tuple(v.shape), problem))
        out[name] = NamedSharding(mesh, spec)
    return out


def _spec_misfit(spec, shape, mesh):
    """None iff every axis in spec exists on the mesh and divides its
    dim; otherwise a human-readable reason string."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    spec_t = tuple(spec)
    if len(spec_t) > len(shape):
        return ("spec has %d entries for a %d-dim shape"
                % (len(spec_t), len(shape)))
    for dim, ax in zip(shape, spec_t + (None,) * (len(shape) - len(spec_t))):
        if ax is None:
            continue
        axs = (ax,) if isinstance(ax, str) else tuple(ax)
        n = 1
        for a in axs:
            if a not in sizes:
                return ("mesh has no axis %r (mesh axes: %s)"
                        % (a, ", ".join(sizes) or "<none>"))
            n *= sizes[a]
        if dim % n != 0:
            return ("dim %d is not divisible by the axis size %d"
                    % (dim, n))
    return None


def parse_rules(text, knob="MXNET_MP_RULES"):
    """Parse the ``MXNET_MP_RULES`` grammar ``'regex:spec;regex:spec'``
    into the ordered ``[(regex, PartitionSpec)]`` list
    :func:`param_shardings` consumes. ``spec`` is a comma list with one
    entry per dim: ``*`` replicates that dim, anything else is a
    mesh-axis name (existence/divisibility are checked at apply time by
    :func:`param_shardings`, which raises :class:`ShardingRuleError`).
    Malformed grammar raises :class:`MXNetError` naming the knob."""
    rules = []
    text = (text or "").strip()
    if not text:
        return rules
    for part in text.split(";"):
        part = part.strip()
        if not part:
            continue
        # rpartition: the regex may contain ':' (e.g. char classes),
        # the spec never does
        pat, sep, spec_s = part.rpartition(":")
        pat = pat.strip()
        if not sep or not pat:
            raise MXNetError(
                "%s: rule %r must be 'regex:spec' with spec a comma "
                "list of '*' or mesh-axis names" % (knob, part))
        try:
            re.compile(pat)
        except re.error as e:
            raise MXNetError(
                "%s: bad regex %r in rule %r: %s" % (knob, pat, part, e))
        entries = []
        for ent in spec_s.split(","):
            ent = ent.strip()
            if not ent:
                raise MXNetError(
                    "%s: empty spec entry in rule %r (use '*' to "
                    "replicate a dim)" % (knob, part))
            entries.append(None if ent == "*" else ent)
        rules.append((pat, P(*entries)))
    return rules


# ---------------------------------------------------------------------------
# functional optimizers (pure mirrors of optimizer.py classes, built on the
# registered pure-JAX update ops in ops/optimizer_ops.py)
# ---------------------------------------------------------------------------
class FunctionalOptimizer:
    """init(params)->state pytree; apply(params, grads, state, step)->new."""

    def __init__(self, init, apply, hyper=None):
        self.init = init
        self.apply = apply
        self.hyper = dict(hyper or {})


def functional_optimizer(name="sgd", learning_rate=0.01, momentum=0.0, wd=0.0,
                         beta1=0.9, beta2=0.999, epsilon=1e-8,
                         rescale_grad=1.0, clip_gradient=None,
                         lr_scheduler=None, wd_pattern=r".*(weight|gamma)$",
                         lr_mult=None, wd_mult=None):
    """Build a pure optimizer. ``wd_pattern``: params matching get weight
    decay, others (bias/beta/moving stats) get 0 — set_wd_mult parity
    (python/mxnet/optimizer.py set_wd_mult). Explicit per-name ``lr_mult``
    / ``wd_mult`` dicts (default multiplier 1.0) override the pattern,
    mirroring Optimizer.set_lr_mult/set_wd_mult exactly."""
    name = name.lower()
    wd_re = re.compile(wd_pattern)

    def lr_at(step):
        if lr_scheduler is not None:
            return lr_scheduler(step)
        return learning_rate

    def mults(k):
        lm = 1.0 if lr_mult is None else float(lr_mult.get(k, 1.0))
        if wd_mult is not None:
            wm = wd * float(wd_mult.get(k, 1.0))
        else:
            wm = wd if wd_re.match(k) else 0.0
        return lm, wm

    def preprocess(g):
        g = g.astype(jnp.float32) * rescale_grad
        if clip_gradient is not None and clip_gradient > 0:
            g = jnp.clip(g, -clip_gradient, clip_gradient)
        return g

    if name == "sgd":
        def init(params):
            if momentum == 0.0:
                return {}
            return {k: jnp.zeros_like(v) for k, v in params.items()}

        def apply(params, grads, state, step):
            lr = lr_at(step)
            new_p, new_s = {}, {}
            for k, w in params.items():
                g = preprocess(grads[k])
                lm, this_wd = mults(k)
                g = g + this_wd * w
                if momentum == 0.0:
                    new_p[k] = w - (lr * lm) * g
                else:
                    m = momentum * state[k] - (lr * lm) * g
                    new_s[k] = m
                    new_p[k] = w + m
            return new_p, new_s

        return FunctionalOptimizer(init, apply, dict(lr=learning_rate, momentum=momentum, wd=wd))

    if name == "adam":
        def init(params):
            return {
                k: (jnp.zeros_like(v), jnp.zeros_like(v)) for k, v in params.items()
            }

        def apply(params, grads, state, step):
            lr = lr_at(step)
            t = step.astype(jnp.float32) + 1.0
            coef1 = 1.0 - beta1 ** t
            coef2 = 1.0 - beta2 ** t
            lr_t = lr * jnp.sqrt(coef2) / coef1
            new_p, new_s = {}, {}
            for k, w in params.items():
                g = preprocess(grads[k])
                lm, this_wd = mults(k)
                g = g + this_wd * w
                m, v = state[k]
                m = beta1 * m + (1 - beta1) * g
                v = beta2 * v + (1 - beta2) * g * g
                new_s[k] = (m, v)
                new_p[k] = w - (lr_t * lm) * m / (jnp.sqrt(v) + epsilon)
            return new_p, new_s

        return FunctionalOptimizer(init, apply, dict(lr=learning_rate, wd=wd))

    raise MXNetError("functional_optimizer: unknown optimizer %r" % name)


def functional_from_optimizer(opt, param_names):
    """Map an imperative ``optimizer.Optimizer`` instance to the pure
    FunctionalOptimizer used by the fused SPMD step (Module kvstore='tpu').

    Raises MXNetError for optimizers/features the fused path cannot
    reproduce exactly (callers fall back to per-executor update).
    """
    from .. import optimizer as opt_mod

    if opt.lr_scheduler is not None:
        raise MXNetError(
            "fused SPMD step: lr_scheduler uses python control flow per "
            "update and cannot be traced; falling back")
    if getattr(opt, "param_dict", None):
        raise MXNetError("fused SPMD step: param_dict mults not supported")
    lr_mult = {n: opt.lr_mult.get(n, 1.0) for n in param_names}
    wd_mult = {n: opt.wd_mult.get(n, 1.0) for n in param_names}
    common = dict(
        learning_rate=opt.lr, wd=opt.wd, rescale_grad=opt.rescale_grad,
        clip_gradient=opt.clip_gradient, lr_mult=lr_mult, wd_mult=wd_mult,
    )
    if type(opt) is opt_mod.SGD:
        return functional_optimizer("sgd", momentum=opt.momentum, **common)
    if type(opt) is opt_mod.Adam:
        return functional_optimizer(
            "adam", beta1=opt.beta1, beta2=opt.beta2, epsilon=opt.epsilon, **common)
    raise MXNetError(
        "fused SPMD step: optimizer %s has no functional mirror"
        % type(opt).__name__)


def cross_entropy_loss(probs, label, eps=1e-12):
    """Mean CE given probabilities (SoftmaxOutput forward emits probs)."""
    lbl = label.astype(jnp.int32).reshape(-1)
    p = probs.reshape(lbl.shape[0], -1)
    picked = jnp.take_along_axis(p, lbl[:, None], axis=-1)
    return -jnp.mean(jnp.log(picked + eps))


# ---------------------------------------------------------------------------
# the fused train step
# ---------------------------------------------------------------------------
class TrainStep:
    """Compiled SPMD training step for a Symbol graph.

    step(carry, batch) -> (carry, loss); carry = (params, opt_state,
    aux, step_no), all device-resident and donated between steps.

    Gradient semantics: gradients flow through the graph exactly as the
    reference's ``Executor::Backward`` with ones head-grads — fused loss
    heads (SoftmaxOutput & co.) substitute their own backward
    (sum-CE gradient), so for such graphs ``loss_fn`` only affects the
    *reported* loss, not the gradients (reference parity:
    src/operator/softmax_output.cc discards out_grad unless out_grad=True).
    ``normalize_grads=True`` (default) divides gradients by global batch
    size, mirroring Module's ``rescale_grad=1/batch`` convention so lr
    values transfer.

    ``zero=True`` (default: the ``MXNET_TPU_ZERO`` knob) turns on
    weight-update sharding (ZeRO / arXiv:2004.13336 — the TPU answer to
    the reference's server-side optimizer, kvstore_dist_server.h): each
    large replicated parameter's update is computed on an explicit
    ``(num_shards, chunk)`` view of its flattened (zero-padded) value,
    with the gradient view constrained to the data axes — the
    reduce-scatter point: XLA materializes each device's 1/N gradient
    shard directly instead of all-reducing the full gradient — the
    optimizer update runs on that 1/N shard (momentum/Adam state lives
    ONLY in its shard between steps, so per-device optimizer-state
    bytes scale 1/N), and the updated shards are constrained back to
    replicated — the all-gather point. Collective volume equals the
    plain all-reduce (RS+AG == AR); memory and update FLOPs drop to
    1/N. Parameters smaller than ``MXNET_TPU_ZERO_MIN_SIZE`` elements
    and tensor-parallel-sharded parameters keep the mirrored path.
    Uneven sizes (``size % N != 0``) are zero-padded; the padding lanes
    provably stay zero under sgd/momentum/adam + wd. With
    ``zero_wire="2bit"`` (``MXNET_TPU_ZERO_WIRE``) the reduce-scattered
    gradient shard additionally round-trips through the PR 4 packed
    two-bit wire codes with a 1/N-sharded error-feedback residual
    (multi-host: this is the quantizer sitting on the reduce-scattered
    DCN wire; single-host: the exact-fidelity simulation, like the
    local tier). The residual is transient — it resets on
    checkpoint restore, matching the server tier's residuals.

    ``sentinel`` (default: the ``MXNET_TPU_SENTINEL`` knob) arms the
    IN-GRAPH anomaly sentinel (ISSUE 9): every step computes a health
    word INSIDE the compiled program — finite loss, finite global
    gradient norm (the grads here are already the mesh-global psum'd
    sums, so the word is identical on every device/host by
    construction), and all-finite updated params — and folds it into
    device-resident counters riding the carry's opt_state under a
    reserved key (the PR 5 device-accumulator pattern: zero per-batch
    host syncs in ``record``/``skip``). ``skip`` additionally turns an
    unhealthy step into a no-op: the pre-update params, optimizer
    state and aux are selected back via ``jnp.where`` (bit-identical
    params, step counter not advanced) and the skip is counted.
    ``halt`` reads the health word on host after EVERY step (the one
    per-batch-sync mode, counted in ``host_syncs``) and raises on the
    first unhealthy step. The counters are transient like the 2-bit
    wire residual: dropped from checkpoints, fresh zeros on restore.
    Drain them with :meth:`health_stats`.

    ``metric_stats=True`` (requires ``return_outputs=True``) additionally
    returns a dict of replicated per-batch metric statistics computed
    INSIDE the compiled program — ``n`` (rows), ``sum_loss`` (loss·n),
    and, for a 2-D first output with a 1-D label, ``correct`` (argmax
    match count) and ``sum_ce`` (summed -log p[label], eps 1e-12,
    mirroring metric.CrossEntropy). The fit loop accumulates these on
    device so no per-batch host sync is needed to keep metrics
    (ISSUE 5 device-resident metrics). Step returns become
    ``(carry, (loss, outputs, stats))``.
    """

    def __init__(self, symbol, optimizer, mesh=None, data_axes=("dp",),
                 param_rules=None, label_names=("softmax_label",),
                 data_names=("data",), compute_dtype=None, loss_fn=None,
                 zero=None, remat=None, normalize_grads=True,
                 return_outputs=False, metric_stats=False, zero_wire=None,
                 zero_min_size=None, sentinel=None):
        from .. import config
        from ..executor import _graph_closure

        self.symbol = symbol
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        # ZeRO knobs (ISSUE 7): explicit ctor args win; None consults the
        # env knobs, which are strictly validated (nonsense raises)
        if zero is None:
            zero = config.get_strict_bool("MXNET_TPU_ZERO")
        self.zero = bool(zero)
        if zero_wire is None:
            zero_wire = config.get_choice("MXNET_TPU_ZERO_WIRE",
                                          ("raw", "2bit"))
        elif zero_wire not in ("raw", "2bit"):
            raise MXNetError("TrainStep: zero_wire=%r must be raw|2bit"
                             % (zero_wire,))
        self.zero_wire = zero_wire
        self.zero_threshold = config.get_positive_float(
            "MXNET_TPU_ZERO_WIRE_THRESHOLD")
        if zero_min_size is None:
            zero_min_size = config.get_nonneg_int("MXNET_TPU_ZERO_MIN_SIZE")
        self.zero_min_size = int(zero_min_size)
        # ISSUE 9: in-graph anomaly sentinel — explicit arg wins, else
        # the strictly-validated knob (nonsense raises at construction)
        if sentinel is None:
            sentinel = config.get_choice("MXNET_TPU_SENTINEL",
                                         ("off", "record", "skip", "halt"))
        elif sentinel not in ("off", "record", "skip", "halt"):
            raise MXNetError("TrainStep: sentinel=%r must be "
                             "off|record|skip|halt" % (sentinel,))
        self.sentinel = sentinel
        self.optimizer = (
            optimizer if isinstance(optimizer, FunctionalOptimizer)
            else functional_optimizer(**optimizer) if isinstance(optimizer, dict)
            else functional_optimizer(optimizer)
        )
        self.label_names = tuple(label_names)
        self.data_names = tuple(data_names)
        self.compute_dtype = compute_dtype
        self.loss_fn = loss_fn or cross_entropy_loss
        # remat — explicit arg wins; None consults the strictly-validated
        # MXNET_TPU_REMAT knob. False/off: no remat; True: full
        # recompute; "conv": prim-name policy.
        if remat is None:
            raw = config.get_choice("MXNET_TPU_REMAT",
                                    ("0", "1", "off", "conv"))
            remat = {"0": False, "off": False, "1": True}.get(raw, raw)
        elif remat not in (False, True, "conv"):
            raise MXNetError(
                "TrainStep: remat=%r must be False|True|'conv'"
                % (remat,))
        self.remat = remat
        self.normalize_grads = normalize_grads
        self.return_outputs = return_outputs
        if metric_stats and not return_outputs:
            raise MXNetError(
                "TrainStep: metric_stats=True requires return_outputs=True")
        self.metric_stats = metric_stats
        self.param_rules = list(param_rules or [])

        arg_names = symbol.list_arguments()
        self.param_names = [
            n for n in arg_names if n not in self.data_names and n not in self.label_names
        ]
        self.aux_names = symbol.list_auxiliary_states()
        self._graph = _graph_closure(symbol, is_train=True)
        self._step_fn = None
        self._jit_fn = None

    # -- initialization ------------------------------------------------------
    def init_params(self, data_shapes, initializer=None, dtype=_np.float32, seed=0):
        """Infer shapes from data shapes and initialize params/aux.

        All allocation happens on the target mesh's first device (or the
        process default when no mesh is set) so that a mesh built from
        non-default devices — e.g. the 8-CPU-device dryrun mesh while the
        default platform is a TPU — never touches the default device.
        """
        from ..initializer import Uniform, InitDesc

        shape_kwargs = dict(data_shapes)
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**shape_kwargs)
        arg_names = self.symbol.list_arguments()
        init = initializer or Uniform(0.01)
        params, aux = {}, {}
        dev = None
        if self.mesh is not None:
            # First *addressable* device: in a multi-host mesh, devices.flat[0]
            # may belong to another process and cannot host allocations.
            pidx = jax.process_index()
            dev = next((d for d in self.mesh.devices.flat if d.process_index == pidx), None)
        ctx = jax.default_device(dev) if dev is not None else contextlib.nullcontext()
        np_state = _np.random.get_state()
        _np.random.seed(seed)
        # the initializer zoo draws from the module-owned RNG
        # (random.initializer_rng), not the global numpy one — seed it
        # too, else same-seed init_params differs across processes
        from .. import random as _rnd_mod

        prev_init_rng = _rnd_mod._INIT_RNG
        _rnd_mod._INIT_RNG = _np.random.RandomState(int(seed) & 0x7FFFFFFF)
        try:
            with ctx:
                for name, shape in zip(arg_names, arg_shapes):
                    if name in self.data_names or name in self.label_names:
                        continue
                    from ..ndarray.ndarray import zeros as nd_zeros

                    arr = nd_zeros(shape, dtype=dtype)
                    init(InitDesc(name), arr)
                    params[name] = arr._data()
                for name, shape in zip(self.aux_names, aux_shapes):
                    val = jnp.ones(shape, dtype) if "var" in name or "gamma" in name else jnp.zeros(shape, dtype)
                    aux[name] = val
                opt_state = self.optimizer.init(params)
        finally:
            _np.random.set_state(np_state)
            _rnd_mod._INIT_RNG = prev_init_rng
        return params, opt_state, aux

    # -- weight-update sharding (ZeRO, ISSUE 7) ------------------------------
    def _zero_axes(self):
        """Mesh axes the weight update shards over (the data axes)."""
        if not self.zero or self.mesh is None:
            return ()
        return tuple(a for a in self.data_axes if a in self.mesh.axis_names)

    def zero_plan(self, params, param_rules=None):
        """{param_name: (shape, size, num_shards, chunk)} for every
        parameter whose update shards over the data axes: replicated by
        the tp rules, at least ``zero_min_size`` (and ``num_shards``)
        elements. Empty when zero is off or the mesh has one device."""
        axes = self._zero_axes()
        if not axes:
            return {}
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        n = 1
        for a in axes:
            n *= sizes[a]
        if n <= 1:
            return {}
        rules = self.param_rules if param_rules is None else param_rules
        ps = param_shardings(params, self.mesh, rules)
        plan = {}
        for k, v in params.items():
            shape = tuple(v.shape)
            if not shape or ps[k].spec != P():
                continue  # scalars and tp-sharded params keep mirrors
            size = 1
            for d in shape:
                size *= int(d)
            if size < max(self.zero_min_size, n):
                continue
            plan[k] = (shape, size, n, -(-size // n))
        return plan

    _ZERO_RES = "__zero_wire_residual__"
    _SENT = "__sentinel_state__"

    @staticmethod
    def _sentinel_init():
        """Fresh device-resident sentinel counters (replicated int32/
        float32 scalars riding opt_state under the reserved key)."""
        z = _np.int32(0)
        return {"healthy": z, "unhealthy": z, "skipped": z, "consec": z,
                "nonfinite_loss": z, "nonfinite_grad": z,
                "nonfinite_param": z, "last_healthy": _np.int32(1),
                "last_loss": _np.float32(0.0)}

    def _ensure_sentinel(self, opt_state):
        """Reconcile the reserved sentinel-counter key with the mode:
        created when armed and missing (idempotent — live counters on
        a re-placed carry survive), dropped when off."""
        if self.sentinel == "off":
            if self._SENT in opt_state:
                opt_state = {k: v for k, v in opt_state.items()
                             if k != self._SENT}
            return opt_state
        if self._SENT in opt_state:
            return opt_state
        out = dict(opt_state)
        out[self._SENT] = self._sentinel_init()
        return out

    @staticmethod
    def _zsplit_np(x, n, chunk):
        """Host-side logical → (num_shards, chunk) zero layout."""
        flat = _np.asarray(x).reshape(-1)
        pad = n * chunk - flat.size
        if pad:
            flat = _np.concatenate([flat, _np.zeros((pad,), flat.dtype)])
        return flat.reshape(n, chunk)

    def _opt_state_to_zero(self, opt_state, plan):
        """Lay optimizer state out for the sharded update: every array
        leaf of a planned param becomes its padded (num_shards, chunk)
        view, and the 2-bit wire residual tree is created when missing.
        Idempotent — leaves already in zero layout pass through, so
        re-placing a live carry (set_params/_replace) is a no-op."""
        if not plan:
            return opt_state
        out = {}
        for k, v in opt_state.items():
            if k == self._ZERO_RES:
                out[k] = v  # live residual: keep it across re-places
                continue
            if k not in plan:
                out[k] = v
                continue
            _shape, _size, n, chunk = plan[k]
            out[k] = jax.tree_util.tree_map(
                lambda x: x if tuple(getattr(x, "shape", ())) == (n, chunk)
                else self._zsplit_np(x, n, chunk), v)
        if self.zero_wire == "2bit":
            # reconcile the residual tree with THIS plan: keep live
            # per-key residuals whose shard shape still matches, zero
            # the rest (a rules change mid-life alters the plan; a
            # stale residual key would KeyError inside the step)
            res = out.get(self._ZERO_RES) or {}
            out[self._ZERO_RES] = {
                k: res[k] if (k in res and tuple(_np.shape(res[k]))
                              == (plan[k][2], plan[k][3]))
                else _np.zeros((plan[k][2], plan[k][3]), _np.float32)
                for k in plan}
        elif self._ZERO_RES in out:
            del out[self._ZERO_RES]  # wire turned off: drop residuals
        return out

    def logical_opt_state(self, opt_state, params, param_rules=None):
        """Zero-layout (host) optimizer state → the mesh-size-independent
        logical layout checkpoints store: planned leaves are un-padded
        and reshaped back to their parameter's shape; the transient wire
        residual is dropped (it resets on restore, like the server
        tier's residuals). The inverse of :meth:`_opt_state_to_zero`, so
        a state saved under ``zero=True`` on N devices restores bit-
        exactly under ``zero=False`` or any other mesh size."""
        plan = self.zero_plan(params, param_rules)
        out = {}
        for k, v in opt_state.items():
            if k in (self._ZERO_RES, self._SENT):
                continue
            if k not in plan:
                out[k] = v
                continue
            shape, size, n, chunk = plan[k]
            out[k] = jax.tree_util.tree_map(
                lambda x: _np.asarray(x).reshape(-1)[:size].reshape(shape)
                if tuple(getattr(x, "shape", ())) == (n, chunk) else x, v)
        return out

    # -- sharding ------------------------------------------------------------
    def shardings(self, params, opt_state, aux, param_rules=None):
        """Shardings for a carry whose opt_state is already in the
        layout :meth:`place` produces (zero keys as (num_shards, chunk)
        views); leaves not in that layout mirror their param."""
        mesh = self.mesh
        if mesh is None:
            return None
        rules = self.param_rules if param_rules is None else param_rules
        ps = param_shardings(params, mesh, rules)
        rep = replicated(mesh)
        plan = self.zero_plan(params, rules)
        axes = self._zero_axes()
        zspec = NamedSharding(mesh, P(axes, None)) if axes else rep

        def opt_shard(k):
            def leaf(x):
                shape = tuple(getattr(x, "shape", ()))
                if k in plan and shape == (plan[k][2], plan[k][3]):
                    return zspec
                if not shape:
                    return rep
                return ps.get(k, rep)
            return leaf

        opt_s = {}
        for k, v in opt_state.items():
            if k == self._ZERO_RES:
                opt_s[k] = jax.tree_util.tree_map(lambda _x: zspec, v)
            else:
                opt_s[k] = jax.tree_util.tree_map(opt_shard(k), v)
        aux_s = {k: rep for k in aux}
        return ps, opt_s, aux_s

    # -- compile -------------------------------------------------------------
    def _loss_closure(self):
        """The (params, aux, batch, key) -> (loss, (outs, aux_updates))
        closure with the remat mode applied — shared between
        :meth:`_build` and :meth:`residual_stats` so the measured
        residual set is exactly the compiled step's."""
        graph = self._graph
        loss_fn = self.loss_fn
        data_names, label_names = self.data_names, self.label_names
        cdtype = self.compute_dtype

        def loss_of(params_c, aux_c, batch, key):
            values = {}
            values.update(params_c)
            values.update(aux_c)
            for n in data_names + label_names:
                values[n] = batch[n]
            if cdtype is not None:
                for n in data_names:
                    values[n] = values[n].astype(cdtype)
            outs, aux_updates = graph(values, key)
            label = batch[label_names[0]] if label_names else None
            loss = loss_fn(outs[0].astype(jnp.float32), label)
            return loss, (outs, aux_updates)

        if self.remat:
            # remat=True: full recompute (the reference's
            # MXNET_BACKWARD_DO_MIRROR). remat="conv": save outputs of the
            # MXU-bound primitives (_SAVEABLE_PRIMS — convs, matmuls AND
            # the custom_vjp/pallas prims the fused units trace as) and
            # recompute the cheap elementwise tail (BN apply, ReLU, pad)
            # inside backward — on a bandwidth-bound graph this trades
            # spare MXU FLOPs for HBM traffic (ROADMAP S8).
            if self.remat == "conv":
                def _policy(prim, *_, **__):
                    return prim.name in _SAVEABLE_PRIMS

                loss_of = jax.checkpoint(loss_of, policy=_policy)
            else:
                loss_of = jax.checkpoint(loss_of, static_argnums=())
        return loss_of

    def residual_stats(self, params, aux, batch, key=None):
        """AD-level backward-residual accounting for the loss under the
        current remat mode (jax's ``saved_residuals``):
        ``residual_bytes`` is the total the backward pass must hold,
        ``n_residuals`` the entry count. This is the remat decision's
        direct, backend-independent measure — XLA's CPU pipeline strips
        optimization barriers and CSE-merges the recompute back into
        the forward, so ``compiled_memory_stats`` on CPU cannot see
        what the TPU compiler (which honors the barriers) does; the
        residual set is what the policy actually changed."""
        # jax 0.9 has no public re-export (jax.ad_checkpoint lacks it);
        # the private module is the only place saved_residuals lives
        from jax._src.ad_checkpoint import saved_residuals

        if key is None:
            from .. import random as _rnd

            key = _rnd.next_key()
        loss_of = self._loss_closure()
        res = saved_residuals(
            lambda p: loss_of(p, aux, batch, key), params)
        total = 0
        for aval, _src in res:
            n = 1
            for d in aval.shape:
                n *= int(d)
            total += n * aval.dtype.itemsize
        return {"residual_bytes": int(total), "n_residuals": len(res)}

    def _build(self, params, opt_state, aux, param_rules=None):
        opt = self.optimizer
        data_names, label_names = self.data_names, self.label_names
        aux_names = list(self.aux_names)
        loss_of = self._loss_closure()
        cdtype = self.compute_dtype

        normalize = self.normalize_grads
        want_stats = self.metric_stats

        # -- ZeRO weight-update sharding (ISSUE 7 tentpole) ------------------
        rules = self.param_rules if param_rules is None else param_rules
        plan = self.zero_plan(params, rules)
        mesh = self.mesh
        zaxes = self._zero_axes()
        zspec = NamedSharding(mesh, P(zaxes, None)) if plan else None
        zrep = replicated(mesh) if plan else None
        wire2bit = bool(plan) and self.zero_wire == "2bit"
        zthresh = self.zero_threshold
        zres_key = self._ZERO_RES

        def zsplit(x, n, chunk, size):
            flat = x.reshape(-1)
            pad = n * chunk - size
            if pad:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((pad,), flat.dtype)])
            return flat.reshape(n, chunk)

        def apply_update(params_c, grads, opt_state_c, step_no):
            """Optimizer update; with a zero plan, the explicit
            reduce-scatter → 1/N-shard update → all-gather. The update
            math is elementwise per key (sgd/momentum/adam/wd/lr_mult),
            so running it on the padded flat view is bit-identical to
            the replicated update on the original shape."""
            if not plan:
                return opt.apply(params_c, grads, opt_state_c, step_no)
            wsc = jax.lax.with_sharding_constraint
            res = opt_state_c.get(zres_key)
            core = {k: v for k, v in opt_state_c.items() if k != zres_key}
            vp, vg, new_res = {}, {}, {}
            for k, w in params_c.items():
                if k not in plan:
                    vp[k] = w
                    vg[k] = grads[k]
                    continue
                _shape, size, n, chunk = plan[k]
                # THE reduce-scatter point: constraining the gradient's
                # (shards, chunk) view to the data axes lets XLA emit a
                # reduce-scatter — each device materializes only its
                # 1/N shard of the gradient sum (arXiv:2004.13336)
                g = wsc(zsplit(grads[k], n, chunk, size), zspec)
                if wire2bit:
                    # PR 4 two-bit quantizer on the reduce-scattered
                    # wire: error-feedback residual is 1/N-sharded too
                    from ..kvstore import two_bit_round_trip_core

                    g, r = two_bit_round_trip_core(
                        g.astype(jnp.float32), res[k], zthresh)
                    new_res[k] = wsc(r, zspec)
                    g = wsc(g, zspec)
                vg[k] = g
                # the replicated param's shard view is a local slice
                vp[k] = wsc(zsplit(w, n, chunk, size), zspec)
            new_p, new_s = opt.apply(vp, vg, core, step_no)
            out_p = {}
            for k, w in new_p.items():
                if k not in plan:
                    out_p[k] = w
                    continue
                shape, size, _n, _chunk = plan[k]
                # THE all-gather point: the updated 1/N shards rebuild
                # the replicated weights for the next forward
                out_p[k] = wsc(w, zrep).reshape(-1)[:size].reshape(shape)
            if wire2bit:
                new_s = dict(new_s)
                new_s[zres_key] = new_res
            return out_p, new_s

        def metric_stats_of(loss, outs, batch):
            """Reducible per-batch metric statistics, computed on the
            sharded global arrays inside the program (cross-shard sums
            compile to the same psum tree as the loss). Counts are int32
            (exact for any epoch < 2^31 rows); sums are float32."""
            out0 = outs[0]
            n_rows = out0.shape[0]
            stats = {
                "n": jnp.asarray(n_rows, jnp.int32),
                "sum_loss": loss.astype(jnp.float32) * n_rows,
            }
            if label_names and label_names[0] in batch:
                label = batch[label_names[0]]
                if (out0.ndim == 2 and label.ndim == 1
                        and label.shape[0] == out0.shape[0]):
                    lbl = label.astype(jnp.int32)
                    probs = out0.astype(jnp.float32)
                    pred = jnp.argmax(probs, axis=-1).astype(jnp.int32)
                    stats["correct"] = jnp.sum(
                        (pred == lbl).astype(jnp.int32))
                    picked = jnp.take_along_axis(
                        probs, lbl[:, None], axis=-1)[:, 0]
                    stats["sum_ce"] = -jnp.sum(jnp.log(picked + 1e-12))
            return stats

        sentinel = self.sentinel
        sent_key = self._SENT

        def health_word(loss, grads, new_params):
            """(healthy, finite_loss, finite_grad, params_ok) — all
            replicated scalars. The grads are the mesh-global psum'd
            sums and params are replicated, so every device (and every
            host in a multi-process mesh) computes the identical word;
            no extra collective is needed beyond the psum the gradients
            already paid for."""
            finite_loss = jnp.isfinite(loss.astype(jnp.float32))
            gsq = jnp.float32(0.0)
            for g in grads.values():
                gsq = gsq + jnp.sum(jnp.square(g.astype(jnp.float32)))
            finite_grad = jnp.isfinite(gsq)
            params_ok = jnp.bool_(True)
            for v in new_params.values():
                if jnp.issubdtype(v.dtype, jnp.floating):
                    params_ok = jnp.logical_and(
                        params_ok, jnp.all(jnp.isfinite(v)))
            healthy = jnp.logical_and(
                jnp.logical_and(finite_loss, finite_grad), params_ok)
            return healthy, finite_loss, finite_grad, params_ok

        def step(carry, batch, key):
            params_c, opt_state_c, aux_c, step_no = carry
            if cdtype is not None:
                cast_params = {k: v.astype(cdtype) for k, v in params_c.items()}
            else:
                cast_params = params_c
            # value_and_grad, taken apart so that the trace tells the
            # forward's operations from the backward's
            with jax.named_scope("mx.step.forward"):
                loss, pullback, (outs, aux_updates) = jax.vjp(
                    lambda p: loss_of(p, aux_c, batch, key), cast_params,
                    has_aux=True)
            with jax.named_scope("mx.step.backward"):
                grads, = pullback(jnp.ones_like(loss))
            if normalize:
                # Module convention: rescale_grad = 1/global_batch (model.py)
                bsz = batch[data_names[0]].shape[0]
                grads = {k: g / bsz for k, g in grads.items()}
            sent = opt_state_c.get(sent_key) if sentinel != "off" else None
            core_opt = opt_state_c if sent is None else \
                {k: v for k, v in opt_state_c.items() if k != sent_key}
            with jax.named_scope("mx.opt.update"):
                new_params, new_opt = apply_update(params_c, grads,
                                                   core_opt, step_no)
            new_aux = dict(aux_c)
            for k, v in aux_updates.items():
                if k in new_aux:
                    new_aux[k] = v.astype(new_aux[k].dtype)
            next_step = step_no + 1
            if sent is not None:
                healthy, f_loss, f_grad, p_ok = health_word(
                    loss, grads, new_params)
                h = healthy.astype(jnp.int32)
                skipped_inc = jnp.int32(0)
                if sentinel == "skip":
                    # unhealthy step becomes a NO-OP: pre-update
                    # params/opt-state/aux selected back (bit-identical
                    # params), the step counter does not advance, and
                    # the skip is counted
                    pick = lambda new, old: jax.tree_util.tree_map(  # noqa: E731
                        lambda n, o: jnp.where(healthy, n, o), new, old)
                    new_params = pick(new_params, params_c)
                    new_opt = pick(new_opt, core_opt)
                    new_aux = pick(new_aux, aux_c)
                    next_step = step_no + h
                    skipped_inc = 1 - h
                one = jnp.int32(1)
                new_opt = dict(new_opt)
                new_opt[sent_key] = {
                    "healthy": sent["healthy"] + h,
                    "unhealthy": sent["unhealthy"] + (one - h),
                    "skipped": sent["skipped"] + skipped_inc,
                    # consecutive-unhealthy run length: resets on a
                    # healthy step (the guard's rollback trigger)
                    "consec": (sent["consec"] + (one - h)) * (one - h),
                    "nonfinite_loss": sent["nonfinite_loss"]
                    + (one - f_loss.astype(jnp.int32)),
                    "nonfinite_grad": sent["nonfinite_grad"]
                    + (one - f_grad.astype(jnp.int32)),
                    "nonfinite_param": sent["nonfinite_param"]
                    + (one - p_ok.astype(jnp.int32)),
                    "last_healthy": h,
                    "last_loss": loss.astype(jnp.float32),
                }
            new_carry = (new_params, new_opt, new_aux, next_step)
            if self.return_outputs:
                if want_stats:
                    return new_carry, (loss, tuple(outs),
                                       metric_stats_of(loss, outs, batch))
                return new_carry, (loss, tuple(outs))
            return new_carry, loss

        if mesh is None:
            self._jit_fn = jax.jit(step, donate_argnums=(0,))
            return self._bind_fused_scope(self._jit_fn)

        # in_shardings reflect the carry layout place() produces: make
        # sure a logical-layout opt_state handed to a raw compile() call
        # yields the same tree (idempotent for the placed carry)
        opt_state = self._opt_state_to_zero(opt_state, plan)
        opt_state = self._ensure_sentinel(opt_state)
        ps, opt_s, aux_s = self.shardings(params, opt_state, aux, param_rules)
        rep = replicated(mesh)
        batch_s = {
            n: data_sharding(mesh, self.data_axes)
            for n in self.data_names + self.label_names
        }
        carry_s = (ps, opt_s, aux_s, rep)
        if self.return_outputs:
            n_out = len(self.symbol.list_outputs())
            out_sh = tuple(data_sharding(mesh, self.data_axes) for _ in range(n_out))
            # `rep` as a pytree PREFIX covers the whole stats dict
            out_s = (carry_s, (rep, out_sh, rep) if want_stats
                     else (rep, out_sh))
        else:
            out_s = (carry_s, rep)
        self._jit_fn = jax.jit(
            step,
            in_shardings=(carry_s, batch_s, rep),
            out_shardings=out_s,
            donate_argnums=(0,),
        )
        return self._bind_fused_scope(self._jit_fn)

    def compile(self, params, opt_state, aux, param_rules=None):
        if param_rules is not None:
            self.param_rules = list(param_rules)
            self._step_fn = None
        if self._step_fn is None:
            self._step_fn = self._build(params, opt_state, aux, self.param_rules)
        return self._step_fn

    def compiled_memory_stats(self, carry, batch, key=None):
        """COMPILED-step memory/cost footprint from XLA's own analyses
        (ISSUE 19) — distinct from :meth:`memory_stats`, which measures
        the resident carry: ``temp_bytes`` is the compiler's peak
        scratch (activations + workspace — the number selective remat
        exists to cut), ``peak_bytes`` adds the non-aliased I/O the
        program holds live. ``flops``/``bytes_accessed`` come from
        ``cost_analysis``."""
        if key is None:
            from .. import random as _rnd

            key = _rnd.next_key()
        self.compile(*carry[:3])
        lower = self._jit_fn.lower
        if self.mesh is not None:
            axes = tuple(a for a in self.data_axes
                         if a in self.mesh.axis_names)
            if axes:
                from ..kernels import fused_block as _fb

                with _fb.spmd_scope(self.mesh, axes):
                    compiled = lower(carry, batch, key).compile()
            else:
                compiled = lower(carry, batch, key).compile()
        else:
            compiled = lower(carry, batch, key).compile()
        mem = compiled.memory_analysis()
        temp = int(getattr(mem, "temp_size_in_bytes", 0))
        arg = int(getattr(mem, "argument_size_in_bytes", 0))
        out = int(getattr(mem, "output_size_in_bytes", 0))
        alias = int(getattr(mem, "alias_size_in_bytes", 0))
        stats = {
            "temp_bytes": temp,
            "argument_bytes": arg,
            "output_bytes": out,
            "alias_bytes": alias,
            "peak_bytes": temp + arg + out - alias,
        }
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if isinstance(cost, dict):
            if cost.get("flops") is not None:
                stats["flops"] = float(cost["flops"])
            if cost.get("bytes accessed") is not None:
                stats["bytes_accessed"] = float(cost["bytes accessed"])
        return stats

    def place(self, params, opt_state, aux, param_rules=None):
        """device_put the carry with its shardings (host → HBM once).
        With ``zero``, optimizer state is laid out as its padded
        (num_shards, chunk) views first — accepts both the logical
        layout (init/checkpoint restore: this is where a checkpoint
        saved on a different mesh size re-splits) and an already-placed
        zero-layout carry (idempotent)."""
        if param_rules is not None:
            self.param_rules = list(param_rules)
            self._step_fn = None
        step_no = jnp.zeros((), jnp.int32)
        if self.mesh is None:
            carry = (params, self._ensure_sentinel(opt_state), aux, step_no)
            self.record_memory_stats(carry)
            return carry
        opt_state = self._opt_state_to_zero(
            opt_state, self.zero_plan(params, self.param_rules))
        opt_state = self._ensure_sentinel(opt_state)
        ps, opt_s, aux_s = self.shardings(params, opt_state, aux, self.param_rules)
        params = {k: jax.device_put(v, ps[k]) for k, v in params.items()}
        opt_state = (
            {k: jax.tree_util.tree_map(lambda x, s: jax.device_put(x, s), v, opt_s[k])
             for k, v in opt_state.items()}
        )
        aux = {k: jax.device_put(v, aux_s[k]) for k, v in aux.items()}
        step_no = jax.device_put(step_no, replicated(self.mesh))
        carry = (params, opt_state, aux, step_no)
        self.record_memory_stats(carry)
        return carry

    # -- memory observability (ISSUE 7) --------------------------------------
    def memory_stats(self, carry):
        """Measured per-device bytes of the resident carry plus analytic
        per-step estimates. ``param/opt/aux_bytes_per_dev`` are MEASURED
        (summed over this process's first mesh device's actual shards);
        ``grad_bytes_per_dev_est`` is the gradient working set the
        update consumes (1/N shards for zero-planned params) and
        ``collective_bytes_per_step_est`` the per-device wire volume of
        the gradient sync (ring all-reduce == reduce-scatter +
        all-gather: 2·size·(N-1)/N either way — ZeRO changes memory,
        not collective volume)."""
        params, opt_state, aux, _step = carry
        dev = None
        if self.mesh is not None:
            pidx = jax.process_index()
            dev = next((d for d in self.mesh.devices.flat
                        if d.process_index == pidx), None)

        def per_dev(tree):
            total = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                shards = getattr(leaf, "addressable_shards", None)
                if shards is None:
                    total += int(getattr(leaf, "nbytes", 0))
                    continue
                d = dev if dev is not None else shards[0].device
                total += sum(int(s.data.nbytes) for s in shards
                             if s.device == d)
            return total

        plan = self.zero_plan(params, self.param_rules)
        grad_est = 0
        coll_est = 0
        n_total = 1
        if self.mesh is not None:
            sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
            for a in self.data_axes:
                n_total *= sizes.get(a, 1)
        for k, v in params.items():
            nbytes = int(_np.prod(tuple(v.shape) or (1,))) * \
                _np.dtype(v.dtype).itemsize
            if k in plan:
                _shape, _size, n, chunk = plan[k]
                grad_est += chunk * _np.dtype(v.dtype).itemsize
            else:
                grad_est += nbytes
            if n_total > 1:
                coll_est += int(2 * nbytes * (n_total - 1) / n_total)
        return {
            "param_bytes_per_dev": per_dev(params),
            "opt_bytes_per_dev": per_dev(opt_state),
            "aux_bytes_per_dev": per_dev(aux),
            "grad_bytes_per_dev_est": int(grad_est),
            "collective_bytes_per_step_est": coll_est,
            "zero": bool(plan),
            "zero_params": len(plan),
            "num_shards": n_total,
        }

    def record_memory_stats(self, carry):
        """Publish :meth:`memory_stats` to the profiler gauge (rides
        ``dump_profile`` as ``memoryStats``)."""
        from .. import profiler

        profiler.memory_record(**self.memory_stats(carry))

    # -- sentinel (ISSUE 9) --------------------------------------------------
    def health_stats(self, carry):
        """Drain the sentinel's device counters from a carry: one
        blocking device read of the replicated scalars (legal on every
        tier — fully-replicated arrays read their local shard). None
        when the sentinel is off."""
        sent = carry[1].get(self._SENT)
        if sent is None:
            return None

        def fetch(x):
            if getattr(x, "is_fully_addressable", True):
                return jax.device_get(x)
            return _np.asarray(x.addressable_data(0))

        vals = {k: fetch(v) for k, v in sent.items()}
        return {k: (float(v) if k == "last_loss" else int(v))
                for k, v in vals.items()}

    def _halt_check(self, new_carry):
        """halt mode: read the health word after every step (the one
        per-batch host sync, recorded honestly) and raise on the first
        unhealthy step."""
        from .. import profiler

        profiler.h2d_record(host_syncs=1)
        snap = self.health_stats(new_carry)
        if snap and not snap["last_healthy"]:
            profiler.health_sentinel(snap)
            raise MXNetError(
                "sentinel halt: unhealthy training step detected "
                "(nonfinite_loss=%d nonfinite_grad=%d nonfinite_param=%d "
                "unhealthy=%d of %d steps, last_loss=%r)"
                % (snap["nonfinite_loss"], snap["nonfinite_grad"],
                   snap["nonfinite_param"], snap["unhealthy"],
                   snap["healthy"] + snap["unhealthy"],
                   snap["last_loss"]))

    def __call__(self, carry, batch, key=None):
        if key is None:
            from .. import random as _rnd

            key = _rnd.next_key()
        fn = self.compile(*carry[:3])
        result = fn(carry, batch, key)
        if self.sentinel == "halt":
            self._halt_check(result[0])
        return result

    def _bind_fused_scope(self, fn):
        """Bind the trace-time SPMD scope for Pallas-fused ops to the
        compiled step: on a mesh, the FusedBottleneckUnit op shard_maps
        its kernels over the data axes (Mosaic kernels are opaque to
        pjit's partitioner on real TPU). The scope wraps every call of
        the returned fn — tracing is lazy, so it must be active at the
        first invocation no matter whether the caller went through
        __call__ or a raw compile()."""
        if self.mesh is None:
            return fn
        axes = tuple(a for a in self.data_axes if a in self.mesh.axis_names)
        if not axes:
            return fn
        from ..kernels import fused_block as _fb

        mesh = self.mesh

        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with _fb.spmd_scope(mesh, axes):
                return fn(*args, **kwargs)

        return scoped
