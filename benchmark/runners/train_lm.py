"""Runner for cells that train the transformer through ``make_train_step``.

Set-up builds one object, the jitted step with its carry, drives it from the
seed through its first ``check_steps`` steps (which also compile the one
program the window uses), reads what ``correct`` compares, and hands the same
carry to the window.  The window dispatches steps on device-resident batches,
keeping ``run_ahead`` steps in flight so that the host never paces the device,
and fetches every loss only after it has closed.
"""
import time

import numpy as np

from benchmark import harness, traffic_gen, weights
from benchmark.compare import leaf_gaps, moving_leaves
from benchmark.reference import opt_lm as ref


class Run:
    def __init__(self, cell, devices, seed, tracer):
        self.cell, self.devices, self.seed, self.tracer = cell, devices, seed, tracer
        self.mix = cell.traffic
        self.model = cell.config["program"]
        self.compute_dtype = self.model["dtype"]
        self.init_std = float(cell.config.get("init_std", weights.INIT_SCALE))
        self.opt = cell.config["optimizer"]
        self.check_steps = int(self.mix["check_steps"])
        self.losses = []
        self.n = 0

    # -- set-up ---------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from mxnet_tpu.models import transformer as tfm
        from mxnet_tpu.parallel.mesh import train_mesh
        from mxnet_tpu.parallel.spmd import functional_optimizer

        weights.check_layout(tfm.init_params, tfm.TransformerConfig)
        if self.opt["name"] != "adam":
            raise SystemExit("train_lm: reads the first gradient from Adam's "
                             "state; optimizer %r has no reading" % self.opt["name"])
        cfg = tfm.TransformerConfig(**self.model)
        mesh = train_mesh(devices=list(self.devices), mp=1)
        self.step, place = tfm.make_train_step(
            cfg, mesh, optimizer=functional_optimizer(**self.opt))
        params = weights.lm_params(self.model, self.seed, self.init_std)
        self.carry = place(params)
        del params
        batches = traffic_gen.token_batches(self.mix, self.model["vocab"], self.seed)
        sharding = NamedSharding(mesh, P("dp", None))
        self.tokens = [jax.device_put(b, sharding) for b in batches]
        self.tokens_per_step = int(batches.shape[1]) * int(self.mix["seq_len"])

        b1 = float(self.opt["beta1"])
        first_moment_norms = jax.jit(lambda state: {
            k: jnp.sqrt(jnp.sum(jnp.square(mv[0]))) / (1.0 - b1)
            for k, mv in state.items()})
        self.read = {}
        for i in range(self.check_steps):
            self._one_step()
            if i == 0:
                # Adam's first moment after one step is (1 - beta1) * g
                self.read["grad1"] = first_moment_norms(self.carry[1])
        self.read["update"] = self._update_norms(self.carry[0])
        self.read["loss"] = list(self.losses)
        jax.block_until_ready((self.carry, self.read))

    def _update_norms(self, params):
        """Per-leaf norm of (params - what the generator made)."""
        import jax
        import jax.numpy as jnp

        norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        return {k: norm(v, weights.lm_leaf(self.model, self.seed, k, self.init_std))
                for k, v in params.items()}

    def _one_step(self):
        with harness.span("bench_train_step_dispatch"):
            self.carry, loss = self.step(self.carry, self.tokens[self.n % len(self.tokens)])
        self.losses.append(loss)
        self.n += 1

    # -- the window -----------------------------------------------------
    def window(self, seconds):
        import jax

        ahead = int(self.mix["run_ahead"])
        first = self.n

        def sync():
            jax.block_until_ready(self.carry)

        def work():
            return {"tokens": (self.n - first) * self.tokens_per_step,
                    "steps": self.n - first}

        t0 = time.perf_counter()
        self.tracer.begin_window(t0)
        while time.perf_counter() - t0 < seconds:
            self._one_step()
            if self.n - first > ahead:
                with harness.span("bench_wait_device"):
                    self.losses[-1 - ahead].block_until_ready()
            self.tracer.poll(work(), sync)
        self.tracer.finish(work(), sync)
        sync()
        elapsed = time.perf_counter() - t0
        done = work()
        self.window_steps = done["steps"]
        return {"train_tokens_per_s": done["tokens"] / elapsed,
                "_elapsed_s": elapsed, "_window_start": t0, "_work": done}

    def release(self):
        """Fetch the losses, then free the program's state."""
        self.window_losses = [float(x) for x in self.losses[self.check_steps:]]
        self.read = {
            "loss": [float(x) for x in self.read["loss"]],
            "grad1": {k: float(v) for k, v in self.read["grad1"].items()},
            "update": {k: float(v) for k, v in self.read["update"].items()}}
        self.carry = self.tokens = self.losses = self.step = None

    # -- correct --------------------------------------------------------
    def reference_readings(self, quant=None, rows=None):
        """The plain reference over the same first steps: losses, first
        gradient norms and the parameters' change, per leaf."""
        import jax
        import jax.numpy as jnp

        kw = {}
        if quant is not None:
            kw["quant"] = quant
        batches = traffic_gen.token_batches(self.mix, self.model["vocab"], self.seed)
        opt = {k: float(self.opt[k]) for k in
               ("learning_rate", "beta1", "beta2", "epsilon")}
        with jax.default_matmul_precision("highest"):
            step = jax.jit(
                lambda p, m, v, t, tok: ref.train_step(p, m, v, t, tok, opt,
                                                       rows=rows, **kw),
                donate_argnums=(0, 1, 2))
            params = weights.lm_params(self.model, self.seed, self.init_std)
            m, v = ref.adam_init(params)
            out = {"loss": [], "grad1": None}
            for i in range(self.check_steps):
                params, m, v, loss, gnorm = step(
                    params, m, v, jnp.float32(i + 1),
                    jnp.asarray(batches[i % len(batches)]))
                out["loss"].append(float(loss))
                if i == 0:
                    out["grad1"] = {k: float(x) for k, x in gnorm.items()}
            del m, v
            out["update"] = {k: float(x)
                             for k, x in self._update_norms(params).items()}
        return out

    @staticmethod
    def compare(got, want):
        """The numbers compared, as (name, value) pairs."""
        rows = []
        for i, (a, b) in enumerate(zip(got["loss"], want["loss"])):
            rows.append(("loss_step%d_rel_gap" % (i + 1), abs(a - b) / abs(b)))
        worst, median = leaf_gaps(got["grad1"], want["grad1"])
        rows += [("grad1_norm_gap_worst_leaf", worst),
                 ("grad1_norm_gap_median_leaf", median)]
        keep = moving_leaves(want["grad1"])
        worst, median = leaf_gaps({k: got["update"][k] for k in keep},
                                  {k: want["update"][k] for k in keep})
        rows += [("update_norm_gap_worst_leaf", worst),
                 ("update_norm_gap_median_leaf", median)]
        return rows

    def check(self):
        want = self.reference_readings()
        limits = self.cell.limits
        checks = [{"name": n, "value": float(v), "limit": float(limits[n])}
                  for n, v in self.compare(self.read, want) if n in limits]
        bad = sum(1 for x in self.window_losses if not np.isfinite(x))
        return checks, self.window_steps, bad
