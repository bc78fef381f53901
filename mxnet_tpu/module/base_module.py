"""BaseModule — the training-loop contract.

Reference counterpart: ``python/mxnet/module/base_module.py`` (fit at
:376-520, score/predict/forward_backward). The fit loop is kept verbatim in
structure: per epoch, per batch → forward_backward → update → update_metric
→ callbacks → checkpoint.
"""
from __future__ import annotations

import logging
import time

import numpy as np

from .. import metric as metric_mod
from .. import profiler
from ..base import MXNetError
from ..io import DataBatch, DataDesc
from ..model import BatchEndParam
from ..ndarray import ndarray as nd


def _as_list(obj):
    if isinstance(obj, list):
        return obj
    return [obj]


def _check_input_names(symbol, names, typename, throw):
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        candidates = [arg for arg in args if not arg.endswith("_weight") and not arg.endswith("_bias") and not arg.endswith("_gamma") and not arg.endswith("_beta")]
        msg = (
            "\033[91mYou created Module with Module(..., %s_names=%s) but input with name '%s' is not found in symbol.list_arguments(). "
            "Did you mean one of:\n\t%s\033[0m" % (typename, str(names), name, "\n\t".join(candidates))
        )
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- high level ----------------------------------------------------------
    def forward_backward(self, data_batch):
        """Fused fwd+bwd (ref: base_module.py:189)."""
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None, batch_end_callback=None,
              score_end_callback=None, reset=True, epoch=0, sparse_row_id_fn=None):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                params = BatchEndParam(epoch=epoch, nbatch=nbatch, eval_metric=eval_metric, locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(params)
            actual_num_batch += 1
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch, eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0 : out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True, reset=True,
                always_output_list=False, sparse_row_id_fn=None):
        assert self.binded and self.params_initialized
        if isinstance(eval_data, (nd.NDArray, np.ndarray)):
            if isinstance(eval_data, np.ndarray):
                eval_data = nd.array(eval_data)
            self.forward(DataBatch(data=[eval_data]), is_train=False)
            return self.get_outputs()[0]
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0 : out.shape[0] - pad].copy() for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs
            output_list2 = [
                nd.concatenate([out[i] for out in output_list]) for i in range(num_outputs)
            ]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None, monitor=None,
            sparse_row_id_fn=None):
        """Train (ref: base_module.py:376-520 — structure preserved)."""
        assert num_epoch is not None, "please specify number of epochs"
        from ..initializer import Uniform

        if initializer is None:
            initializer = Uniform(0.01)

        self.bind(
            data_shapes=train_data.provide_data,
            label_shapes=train_data.provide_label,
            for_training=True,
            force_rebind=force_rebind,
        )
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(
            initializer=initializer, arg_params=arg_params, aux_params=aux_params,
            allow_missing=allow_missing, force_init=force_init,
        )
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer, optimizer_params=optimizer_params)

        # self-healing guardrail (ISSUE 9): armed when the job has a
        # coordinated checkpoint directory (MXNET_CHECKPOINT_DIR —
        # launch.py exports it) and MXNET_TPU_GUARD=1 (default). It
        # watches health at a bounded cadence, rolls back to the last
        # committed checkpoint with LR backoff on sustained anomalies,
        # and turns a SIGTERM into a grace-window checkpoint + a
        # resumable exit the supervision respawns for free (health.py).
        from ..health import HealthGuard

        health_guard = HealthGuard.from_env(
            self, kv=getattr(self, "_kvstore", None), logger=self.logger)
        if health_guard is not None:
            health_guard.install_preemption_handler()

        # background tuning (ISSUE 15): armed by MXNET_TUNE_BACKGROUND=1.
        # Steals one bounded tuning slot per epoch at the drain boundary
        # below (after get_params emptied the dispatch-ahead pipeline)
        # for shapes this job traced but the schedule table missed —
        # never inside the steady-state step loop (tune/background.py).
        from ..tune.background import BackgroundTuner

        bg_tuner = BackgroundTuner.from_env(logger=self.logger)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)

        # the fused step takes its batches already on the mesh: put the
        # device queue between the caller's iterator and this loop, so the
        # slice and copy of batch n+1 run on the queue's worker while step
        # n computes. fit owns the wrapper: every reset goes through it and
        # it is closed on the way out, never the caller's iterator, which
        # stays usable for another fit
        from ..parallel.feed import DeviceQueueIter

        feed = None
        if (getattr(self, "_fused", None) is not None
                and not isinstance(train_data, DeviceQueueIter)):
            train_data = feed = DeviceQueueIter(train_data, module=self,
                                                close_source=False)
        try:
            for epoch in range(begin_epoch, num_epoch):
                tic = time.time()
                eval_metric.reset()
                nbatch = 0
                data_iter = iter(train_data)
                end_of_batch = False
                next_data_batch = next(data_iter)
                while not end_of_batch:
                    with profiler.span("mx.fit.batch", epoch=epoch, nbatch=nbatch):
                        data_batch = next_data_batch
                        if monitor is not None:
                            monitor.tic()
                        with profiler.span("mx.fit.forward_backward"):
                            self.forward_backward(data_batch)
                        with profiler.span("mx.fit.update"):
                            self.update()
                        with profiler.span("mx.fit.next_batch"):
                            try:
                                next_data_batch = next(data_iter)
                                self.prepare(next_data_batch,
                                             sparse_row_id_fn=sparse_row_id_fn)
                            except StopIteration:
                                end_of_batch = True
                        with profiler.span("mx.fit.update_metric"):
                            self.update_metric(eval_metric, data_batch.label)
                        if health_guard is not None:
                            # batch-boundary health/preemption hook: may roll
                            # the module back to the latest checkpoint, or
                            # raise SystemExit(EXIT_PREEMPTED) after a
                            # grace-window checkpoint
                            health_guard.on_batch(epoch, nbatch, eval_metric,
                                                  data_batch.label)
                        if monitor is not None:
                            monitor.toc_print()
                        if batch_end_callback is not None:
                            batch_end_params = BatchEndParam(
                                epoch=epoch, nbatch=nbatch, eval_metric=eval_metric,
                                locals=locals()
                            )
                            with profiler.span("mx.fit.callbacks"):
                                for callback in _as_list(batch_end_callback):
                                    callback(batch_end_params)
                        nbatch += 1

                for name, val in eval_metric.get_name_value():
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
                toc = time.time()
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))

                with profiler.span("mx.fit.epoch_end", epoch=epoch):
                    arg_params_, aux_params_ = self.get_params()
                    self.set_params(arg_params_, aux_params_)

                if bg_tuner is not None:
                    # drained boundary: get_params() above blocked on the
                    # dispatch-ahead pipeline, so the tuner's bounded slot
                    # cannot overlap a steady-state step; winners commit
                    # atomically and the next trace of this shape picks
                    # them up
                    bg_tuner.on_drain()

                if epoch_end_callback is not None:
                    for callback in _as_list(epoch_end_callback):
                        callback(epoch, self.symbol, arg_params_, aux_params_)

                if eval_data is not None:
                    res = self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback, epoch=epoch,
                    )
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f", epoch, name, val)

                train_data.reset()
        finally:
            if feed is not None:
                feed.close()

    # -- abstract ------------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False, force_init=True, allow_extra=False):
        self.init_params(
            initializer=None, arg_params=arg_params, aux_params=aux_params,
            allow_missing=allow_missing, force_init=force_init, allow_extra=allow_extra,
        )

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        from ..ndarray.utils import save

        save(fname, save_dict)

    def load_params(self, fname):
        from ..ndarray.utils import load

        save_dict = load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None, grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),), force_init=False):
        raise NotImplementedError()

    def prepare(self, data_batch, sparse_row_id_fn=None):
        pass

    def install_monitor(self, mon):
        raise NotImplementedError()
