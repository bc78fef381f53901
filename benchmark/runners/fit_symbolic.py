"""Runner for cells that train a symbol through ``Module.fit(kvstore='tpu')``,
fed from the host.

One ``fit`` call of two epochs over a benchmark-owned iterator.  The first
epoch is set-up: ``check_steps`` batches that compile the step and give what
``correct`` compares (each batch's loss, the first gradient from the momentum
after one step, the parameters' change after the last), then ``fit``'s own
epoch end.  The second epoch is the window: the iterator cycles the seeded
host batches until the deadline, and ``batch_end_callback`` reads the metric,
blocking, as ``fit``'s users do (``Speedometer``).  The rate is all images of
all batches whose callback fell in the window over the time from the epoch's
first ``next`` to the last callback.
"""
import time

import numpy as np

from benchmark import harness, traffic_gen, weights
from benchmark.reference import resnet as ref
from benchmark.compare import leaf_gaps


class Run:
    def __init__(self, cell, devices, seed, tracer):
        self.cell, self.devices, self.seed, self.tracer = cell, devices, seed, tracer
        self.mix = cell.traffic
        self.net = cell.config["network"]
        self.opt = cell.config["optimizer"]
        self.check_steps = int(self.mix["check_steps"])
        self.batch = int(self.mix["batch"])
        self.compute_dtype = cell.config["compute_dtype"]
        self._drawn = None

    def _inputs(self):
        """The seed's images and labels, all batches in a row; drawn once (the
        reference reads the benchmark's own copy, nothing the program made)."""
        if self._drawn is None:
            data, label = traffic_gen.image_batches(self.mix, self.net["num_classes"],
                                                    self.seed)
            self._drawn = (data.reshape((-1,) + data.shape[2:]), label.reshape(-1))
        return self._drawn

    # -- set-up: everything before ``fit`` ---------------------------------
    def setup(self):
        import mxnet_tpu as mx
        from mxnet_tpu.models import resnet

        shapes = ref.param_shapes(self.net)
        sym = resnet.get_symbol(num_classes=self.net["num_classes"],
                                num_layers=self.net["num_layers"],
                                image_shape=tuple(self.net["image_shape"]))
        theirs = dict(zip(sym.list_arguments(), sym.infer_shape(
            data=(self.batch,) + tuple(self.net["image_shape"]))[0]))
        for k, s in shapes[0].items():
            if tuple(theirs.get(k, ())) != tuple(s):
                raise SystemExit("fit_symbolic: the symbol's %s is %r, the "
                                 "benchmark makes %r" % (k, theirs.get(k), s))
        self.args0, aux0 = weights.resnet_params(shapes, self.seed)
        self.mx = mx
        self.mod = mx.mod.Module(
            sym, context=[mx.tpu(i) for i in range(len(self.devices))],
            compute_dtype=self.compute_dtype)
        self.arg_params = {k: mx.nd.array(v) for k, v in self.args0.items()}
        self.aux_params = {k: mx.nd.array(v) for k, v in aux0.items()}
        data, label = self._inputs()
        self.feed = PhasedIter(mx, mx.io.NDArrayIter(data, label, batch_size=self.batch),
                               self.check_steps)
        self.read = {"loss": [], "grad1": None, "update": None}
        self.times = []
        self.done = {"images": 0, "batches": 0, "put_seconds": 0.0}

    # -- readings, from the fused step's own carry -------------------------
    def _carry(self):
        group = self.mod._fused
        if group is None:
            raise SystemExit("fit_symbolic: kvstore='tpu' did not engage the fused step")
        return group._carry

    def _read_grad1(self):
        import jax
        import jax.numpy as jnp

        lr = float(self.opt["learning_rate"])
        _params, mom, _aux, _step = self._carry()
        self.read["grad1"] = jax.jit(lambda m: {
            k: jnp.sqrt(jnp.sum(jnp.square(v))) / lr for k, v in m.items()})(mom)

    def _read_update(self):
        import jax
        import jax.numpy as jnp

        params = self._carry()[0]
        first = {k: jnp.asarray(v) for k, v in self.args0.items()}
        self.read["update"] = jax.jit(lambda p, q: {
            k: jnp.sqrt(jnp.sum(jnp.square(p[k] - q[k]))) for k in q})(params, first)

    def _pipeline(self):
        from mxnet_tpu import profiler

        return float(profiler.pipeline_stats().get("put_seconds", 0.0))

    def _on_batch(self, param):
        import jax

        with harness.span("bench_metric_read"):
            loss = float(param.eval_metric.get()[1])          # blocks, as Speedometer
        param.eval_metric.reset()
        now = time.perf_counter()
        if param.epoch == 0:
            self.read["loss"].append(loss)
            if param.nbatch == 0:
                self._read_grad1()
            if param.nbatch == self.check_steps - 1:
                self._read_update()
                self.put0 = self._pipeline()
            return
        self.window_losses.append(loss)
        self.times.append(now)
        self.done = {"images": len(self.times) * self.batch,
                     "batches": len(self.times), "put_seconds": self._pipeline()}
        self.tracer.poll(self.done, lambda: jax.block_until_ready(self._carry()))

    # -- the window: ``fit`` itself -------------------------------------------
    def window(self, seconds):
        import jax

        mx = self.mx
        self.window_losses = []
        self.feed.seconds = seconds
        self.feed.on_window = self.tracer.begin_window
        opt = {k: float(self.opt[k]) for k in ("learning_rate", "momentum", "wd")}
        self.mod.fit(self.feed, num_epoch=2, kvstore="tpu", optimizer="sgd",
                     optimizer_params=opt, arg_params=self.arg_params,
                     aux_params=self.aux_params,
                     eval_metric=mx.metric.CrossEntropy(),
                     batch_end_callback=self._on_batch)
        self.tracer.finish(self.done, lambda: jax.block_until_ready(self._carry()))
        t0 = self.feed.window_start
        elapsed = self.times[-1] - t0
        per_batch = np.diff([t0] + self.times) * 1e3
        print("batch_ms p05=%.1f p50=%.1f p95=%.1f max=%.1f; host put %.1f ms a batch"
              % (*np.percentile(per_batch, [5, 50, 95]), per_batch.max(),
                 1e3 * (self.done["put_seconds"] - self.put0) / self.done["batches"]),
              flush=True)
        return {"train_images_per_s": self.done["images"] / elapsed,
                "_elapsed_s": elapsed, "_window_start": t0, "_work": self.done}

    def release(self):
        self.read = {
            "loss": list(self.read["loss"]),
            "grad1": {k: float(v) for k, v in self.read["grad1"].items()},
            "update": {k: float(v) for k, v in self.read["update"].items()}}
        self.mod = self.feed = self.arg_params = self.aux_params = None

    # -- correct --------------------------------------------------------------
    def reference_readings(self, quant=None, rows=None):
        import jax
        import jax.numpy as jnp

        kw = {} if quant is None else {"quant": quant}
        data, label = self._inputs()
        opt = {k: float(self.opt[k]) for k in ("learning_rate", "momentum", "wd")}
        net = self.net
        with jax.default_matmul_precision("highest"):
            step = jax.jit(
                lambda p, m, im, lb: ref.train_step(p, m, net, im, lb, opt,
                                                    rows=rows, **kw),
                donate_argnums=(0, 1))
            params = {k: jnp.asarray(v) for k, v in self.args0.items()}
            mom = {k: jnp.zeros_like(v) for k, v in params.items()}
            out = {"loss": [], "grad1": None}
            for i in range(self.check_steps):
                lo = (i * self.batch) % len(label)
                params, mom, loss, gnorm = step(
                    params, mom, jnp.asarray(data[lo:lo + self.batch]),
                    jnp.asarray(label[lo:lo + self.batch].astype(np.int32)))
                out["loss"].append(float(loss))
                if i == 0:
                    out["grad1"] = {k: float(x) for k, x in gnorm.items()}
            out["update"] = {
                k: float(jnp.sqrt(jnp.sum(jnp.square(params[k] - self.args0[k]))))
                for k in params}
        return out

    def compare(self, got, want):
        """The numbers compared, as (name, value) pairs.  ``matrix`` leaves
        are the operands of products (rank 2 and over: convolution and
        classifier weights); the others are BatchNorm's vectors and the
        classifier's bias."""
        matrix = [k for k, shape in ref.param_shapes(self.net)[0].items()
                  if len(shape) >= 2]
        rows = []
        for i, (a, b) in enumerate(zip(got["loss"], want["loss"])):
            rows.append(("loss_step%d_rel_gap" % (i + 1), abs(a - b) / abs(b)))
        for what in ("grad1", "update"):
            worst, median = leaf_gaps(got[what], want[what])
            rows += [(what + "_norm_gap_worst_leaf", worst),
                     (what + "_norm_gap_median_leaf", median),
                     (what + "_norm_gap_median_matrix_leaf",
                      leaf_gaps(got[what], want[what], only=matrix)[1])]
        return rows

    def check(self):
        want = self.reference_readings()
        limits = self.cell.limits
        checks = [{"name": n, "value": float(v), "limit": float(limits[n])}
                  for n, v in self.compare(self.read, want) if n in limits]
        bad = sum(1 for x in self.window_losses if not np.isfinite(x))
        return checks, len(self.window_losses), bad


class PhasedIter:
    """The benchmark's iterator: epoch 0 yields ``warm`` batches and ends;
    epoch 1 cycles the inner iterator until ``seconds`` after its first
    ``next``, which is where the window starts."""

    def __init__(self, mx, inner, warm):
        self.inner, self.warm = inner, int(warm)
        self.epoch, self.given = 0, 0
        self.seconds = None
        self.window_start = None
        self.on_window = None
        self.batch_size = inner.batch_size

    @property
    def provide_data(self):
        return self.inner.provide_data

    @property
    def provide_label(self):
        return self.inner.provide_label

    def reset(self):
        self.inner.reset()
        self.epoch += 1
        self.given = 0

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def next(self):
        with harness.span("bench_iter_next"):
            now = time.perf_counter()
            if self.epoch == 0:
                if self.given >= self.warm:
                    raise StopIteration
            else:
                if self.window_start is None:
                    self.window_start = now
                    if self.on_window:
                        self.on_window(now)
                if now - self.window_start >= self.seconds:
                    raise StopIteration
            self.given += 1
            try:
                return self.inner.next()
            except StopIteration:
                self.inner.reset()
                return self.inner.next()
