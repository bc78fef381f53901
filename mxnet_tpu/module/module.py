"""Module — symbol + executor group + optimizer intermediate API.

Reference counterpart: ``python/mxnet/module/module.py:39-736`` (bind →
DataParallelExecutorGroup, init_params, init_optimizer with kvstore,
update via _update_params_on_kvstore).
"""
from __future__ import annotations

import logging
import warnings

from .. import context as ctx_mod
from .. import optimizer as opt_mod
from ..base import MXNetError
from ..initializer import InitDesc, Uniform
from ..io import DataDesc
from ..model import (
    _create_kvstore,
    _initialize_kvstore,
    _update_params,
    _update_params_on_kvstore,
    load_checkpoint,
    save_checkpoint,
)
from ..ndarray import ndarray as nd
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None, group2ctxs=None,
                 compression_params=None, zero=None, compute_dtype=None):
        super().__init__(logger=logger)
        # ISSUE 7: weight-update sharding on the fused tier. True/False
        # forces it; None defers to the MXNET_TPU_ZERO env knob — so
        # Module.fit users get ZeRO without touching jax.
        self._zero = zero
        # mixed precision on the fused step (kvstore='tpu'): forward and
        # backward in this dtype, fp32 masters. Only the fused step can
        # honour it — init_optimizer raises rather than train in fp32 on
        # the per-executor path.
        self._compute_dtype = compute_dtype
        if context is None:
            context = ctx_mod.current_context()
        if isinstance(context, ctx_mod.Context):
            context = [context]
        self._context = context
        # per-device ctx-group maps (ref: module.py group2ctxs — a dict
        # shared by all devices, or a list of dicts, one per device)
        if isinstance(group2ctxs, dict) or group2ctxs is None:
            group2ctxs = [group2ctxs] * len(self._context)
        assert len(group2ctxs) == len(self._context)
        self._group2ctxs = group2ctxs
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        assert len(work_load_list) == len(self._context)
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = list(fixed_param_names or [])
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = list(state_names or [])
        self._output_names = symbol.list_outputs()

        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, self._state_names, "state", True)
        _check_input_names(symbol, self._fixed_param_names, "fixed_param", True)

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._compression_params = compression_params
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._exec_group = None
        self._fused = None
        self._data_shapes = None
        self._label_shapes = None

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        self._sync_params_from_devices()
        save_checkpoint(prefix, epoch, self.symbol, *self.get_params())
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)

    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        if self._fused is not None:
            # the fused group owns the device-resident optimizer state:
            # dropping it must force init_optimizer to rebuild the
            # group, else a re-bound fit() silently trains unfused
            self.optimizer_initialized = False
        self._fused = None
        self._data_shapes = None
        self._label_shapes = None

    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._exec_group.data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._exec_group.label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        # inferred once per bind/reshape (ref: module.py output_shapes
        # comes from the bound graph's inferred shapes, not a forward)
        key = tuple(self._exec_group._total_data_shapes
                    + self._exec_group._total_label_shapes)
        cached = getattr(self, "_output_shape_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        _, out_shapes, _ = self._symbol.infer_shape(**dict(key))
        result = list(zip(self._output_names,
                          [tuple(s) for s in out_shapes]))
        self._output_shape_cache = (key, result)
        return result

    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and force_init=False. "
                          "init_params call ignored.", stacklevel=2)
            return
        assert self.binded, "call bind before initializing the parameters"
        if initializer is None:
            initializer = Uniform(0.01)

        def _impl(name, arr, cache):
            if cache is not None:
                if name in cache:
                    cache_arr = cache[name]
                    if cache_arr is not arr:
                        cache_arr.copyto(arr)
                else:
                    if not allow_missing:
                        raise RuntimeError("%s is not presented" % name)
                    if initializer is not None:
                        initializer(InitDesc(name, self._arg_attrs.get(name)), arr)
            else:
                initializer(InitDesc(name, self._arg_attrs.get(name)), arr)

        attrs = self._symbol.attr_dict()
        self._arg_attrs = {n: attrs.get(n, {}) for n in self._param_names + self._aux_names}

        if self._arg_params is None:
            self._arg_params = {
                name: nd.zeros(block[0].shape, dtype=block[0].dtype)
                for name, block in zip(self._param_names, self._exec_group.param_arrays)
            }
        if self._aux_params is None:
            self._aux_params = {
                name: nd.zeros(block[0].shape, dtype=block[0].dtype)
                for name, block in zip(self._aux_names, self._exec_group.aux_arrays)
            }

        for name, arr in sorted(self._arg_params.items()):
            _impl(name, arr, arg_params)
        for name, arr in sorted(self._aux_params.items()):
            _impl(name, arr, aux_params)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params, allow_extra=allow_extra)
        if self._fused is not None:
            self._fused.set_params(self._arg_params, self._aux_params)

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None, grad_req="write"):
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        if not for_training:
            assert not inputs_need_grad

        self._data_shapes = data_shapes
        self._label_shapes = label_shapes

        shared_group = None
        if shared_module is not None:
            assert shared_module.binded and shared_module.params_initialized
            shared_group = shared_module._exec_group

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list, data_shapes,
            label_shapes, self._param_names, for_training, inputs_need_grad,
            shared_group, logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=grad_req, state_names=self._state_names,
            group2ctxs=self._group2ctxs,
        )
        if shared_module is not None:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        # host copies must be refreshed from the *old* executors before
        # they are replaced
        if self.params_initialized and self._params_dirty:
            self._sync_params_from_devices()
        self._data_shapes = data_shapes
        self._label_shapes = label_shapes
        self._exec_group.reshape(data_shapes, label_shapes)
        # rebinding allocated fresh (zeroed) arg arrays — restore weights
        # (ref: reshape shares the original arrays; here buffers are new)
        if self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),), force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        if self._params_dirty:
            self._sync_params_from_devices()

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params
        )
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_async" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        idx2name = {}
        if update_on_kvstore:
            idx2name.update(enumerate(self._exec_group.param_names))
        else:
            for k in range(len(self._context)):
                idx2name.update(
                    {i * len(self._context) + k: n for i, n in enumerate(self._exec_group.param_names)}
                )
        if isinstance(optimizer, str):
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt_mod.create(optimizer, sym=self.symbol,
                                       param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt_mod.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                warnings.warn(
                    "Optimizer created manually outside Module but rescale_grad "
                    "is not normalized to 1.0/batch_size/num_workers (%s vs. %s). "
                    % (optimizer.rescale_grad, rescale_grad)
                )
            if not optimizer.idx2name:
                optimizer.idx2name = idx2name.copy()

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        # kvstore='tpu': run the whole train step (fwd+bwd+update) as one
        # compiled SPMD program over a mesh built from the context list —
        # the TPU answer to the reference's DataParallelExecutorGroup +
        # Comm reduce (module.py:468-530, comm.h). Falls back to the
        # per-executor path for optimizers the fused step can't mirror.
        self._fused = None
        fused_types = ("tpu", "dist_sync", "dist_sync_device", "dist_async")
        if (kvstore is not None and kvstore.type in fused_types
                and not getattr(kvstore, "server_side", False)
                and self.for_training):
            from .spmd_group import FusedSPMDGroup

            distributed = kvstore.type.startswith("dist")
            try:
                self._fused = FusedSPMDGroup(
                    self._symbol, self._context, self._optimizer,
                    self._arg_params, self._aux_params,
                    self._data_names, self._label_names,
                    fixed_param_names=self._fixed_param_names,
                    logger=self.logger,
                    batch_size=self._exec_group.batch_size,
                    inputs_need_grad=self.inputs_need_grad,
                    distributed=distributed,
                    zero=self._zero,
                    compute_dtype=self._compute_dtype,
                )
            except MXNetError as e:
                # typed: a request the fused step cannot mirror (optimizer,
                # fixed params, ...). Anything else — mesh or device
                # construction failing — is an error and propagates.
                self.logger.warning(
                    "kvstore=%r: %s; using per-executor update path",
                    kvstore.type, e)
                self._fused = None
            else:
                if hasattr(kvstore, "attach_mesh"):
                    kvstore.attach_mesh(self._fused.mesh)
                update_on_kvstore = False
                self._update_on_kvstore = False
        if self._compute_dtype is not None and self._fused is None:
            raise MXNetError(
                "Module(compute_dtype=%r) needs the fused train step "
                "(kvstore='tpu' or dist_sync, for_training); the "
                "per-executor path with kvstore=%r computes in the "
                "parameters' dtype"
                % (self._compute_dtype, getattr(kvstore, "type", None)))

        if self._fused is not None and getattr(self, "_monitor_installed",
                                               False):
            self._warn_monitor_on_fused()

        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            _initialize_kvstore(
                kvstore=kvstore, param_arrays=self._exec_group.param_arrays,
                arg_params=self._arg_params, param_names=self._param_names,
                update_on_kvstore=update_on_kvstore,
            )
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt_mod.get_updater(optimizer)

        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if self._fused is not None:
            self._last_fused = False
            if self._params_dirty:
                # eval/predict goes through the per-ctx executors: refresh
                # them (and the host copies) from the fused device carry.
                self._sync_params_from_devices()
        curr_data_shapes = tuple(i.shape for i in self._exec_group.data_shapes)
        if isinstance(data_batch, list):
            new_data_shapes = tuple(b.data[0].shape for b in data_batch)
        else:
            new_data_shapes = tuple(i.shape for i in data_batch.data)
        if curr_data_shapes != new_data_shapes:
            if hasattr(data_batch, "provide_data") and data_batch.provide_data:
                new_dshape = data_batch.provide_data
            else:
                new_dshape = [
                    DataDesc(i.name, shape, i.dtype, i.layout)
                    for i, shape in zip(self._exec_group.data_shapes, new_data_shapes)
                ]
            if hasattr(data_batch, "provide_label") and data_batch.provide_label:
                new_lshape = data_batch.provide_label
            elif hasattr(data_batch, "label") and data_batch.label:
                new_lshape = [
                    DataDesc(i.name, j.shape, i.dtype, i.layout)
                    for i, j in zip(self._exec_group.label_shapes, data_batch.label)
                ]
            else:
                new_lshape = None
            self.reshape(new_dshape, new_lshape)
        self._exec_group.forward(data_batch, is_train)

    def forward_backward(self, data_batch):
        assert self.binded and self.params_initialized
        if self._fused is not None:
            # One compiled step: fwd+bwd+optimizer update, batch sharded
            # over the mesh. update() below becomes a no-op.
            if getattr(self, "_fused_stale", False):
                # an explicit forward/backward/update() round went through
                # the per-executor path meanwhile: refresh the device carry
                self._exec_group.get_params(self._arg_params, self._aux_params)
                self._fused.set_params(self._arg_params, self._aux_params)
                self._fused_stale = False
            self._fused.forward_backward_update(data_batch)
            from .. import chaos

            chaos.tick_step()  # fused step = one worker chaos step (the
            # per-executor paths tick inside model._update_params*)
            self._params_dirty = True
            self._last_fused = True
            return
        self._exec_group.forward_backward(data_batch)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec_group.backward(out_grads=out_grads)

    def update(self):
        assert self.binded and self.params_initialized and self.optimizer_initialized
        self._params_dirty = True
        if self._fused is not None:
            if getattr(self, "_last_fused", False):
                return  # update already applied inside the fused step
            # explicit forward()/backward() round: apply the per-executor
            # update and mark the fused carry stale so the next fused step
            # reloads parameters from the executors.
            self._fused_stale = True
        if self._update_on_kvstore:
            _update_params_on_kvstore(
                self._exec_group.param_arrays, self._exec_group.grad_arrays,
                self._kvstore, self._exec_group.param_names,
            )
        else:
            _update_params(
                self._exec_group.param_arrays, self._exec_group.grad_arrays,
                updater=self._updater, num_device=len(self._context),
                kvstore=self._kvstore, param_names=self._exec_group.param_names,
            )

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._fused is not None and getattr(self, "_last_fused", False):
            return self._fused.get_outputs()
        return self._exec_group.get_outputs(merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context=merge_multi_context)

    def update_metric(self, eval_metric, labels):
        if self._fused is not None and getattr(self, "_last_fused", False):
            self._fused.update_metric(eval_metric, labels)
            return
        self._exec_group.update_metric(eval_metric, labels)

    def _sync_params_from_devices(self):
        if self._fused is not None:
            if getattr(self, "_fused_stale", False):
                # per-executor update ran last: executors hold the truth
                self._exec_group.get_params(self._arg_params, self._aux_params)
                self._fused.set_params(self._arg_params, self._aux_params)
                self._fused_stale = False
            else:
                self._fused.copy_params_to(self._arg_params, self._aux_params)
                self._exec_group.set_params(self._arg_params, self._aux_params)
            self._params_dirty = False
            return
        self._exec_group.get_params(self._arg_params, self._aux_params)
        if self._kvstore and self._update_on_kvstore:
            # ONE batched pull (per-shard multi-key frames on the
            # server tier) instead of a round trip per parameter
            names = sorted(self._arg_params)
            if names:
                self._kvstore.pull(names,
                                   [self._arg_params[n] for n in names],
                                   priority=0)
        self._params_dirty = False

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        from ..checkpoint import atomic_write_bytes

        # every branch writes tmp-fsync-rename: a crash mid-save must
        # never leave a torn .states file (ISSUE 3 satellite)
        if self._fused is not None:
            atomic_write_bytes(fname, self._fused.get_states())
            return
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            atomic_write_bytes(fname, self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._fused is not None:
            self._fused.set_states(open(fname, "rb").read())
            return
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            self._updater.set_states(open(fname, "rb").read())

    def install_monitor(self, mon):
        assert self.binded
        self._monitor_installed = True
        if self._fused is not None:
            self._warn_monitor_on_fused()
        self._exec_group.install_monitor(mon)

    def _warn_monitor_on_fused(self):
        # loud, not fatal: the job still trains — but the monitor's
        # callbacks never fire inside the fused program AND its
        # tic/toc host syncs defeat the stall-free loop; the in-graph
        # sentinel is the fused-tier tool (see monitor.py docstring)
        self.logger.warning(
            "Monitor is installed but this Module trains through the "
            "fused SPMD step (kvstore='tpu' tier): per-op monitor "
            "callbacks never run inside the compiled program, and "
            "Monitor's per-batch host syncs would defeat the "
            "stall-free fit loop anyway. Use the in-graph sentinel "
            "(MXNET_TPU_SENTINEL=record|skip|halt) and profiler "
            "healthStats instead.")

    def prepare(self, data_batch, sparse_row_id_fn=None):
        assert self.binded
        if sparse_row_id_fn is not None and self._kvstore is not None:
            row_ids = sparse_row_id_fn(data_batch)
            for name, rid in row_ids.items():
                if name in self._param_names:
                    idx = self._param_names.index(name)
                    self._kvstore.row_sparse_pull(
                        name, out=self._exec_group.param_arrays[idx], row_ids=rid
                    )
