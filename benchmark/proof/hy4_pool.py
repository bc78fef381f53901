#!/usr/bin/env python3
"""The proof scripts of the decode-pool cells with Hy4-preview's cell in
GLM-5's place (they name their cell in a module constant, so each is loaded
here as a private copy and given the other name), and the builds planted
with a fault that the comparison must catch:

    python3 benchmark/proof/hy4_pool.py rehearse --seed 7 --seconds 0.3 --trace 0
    python3 benchmark/proof/hy4_pool.py readings --seeds 1 --first-seed 4400001000
    python3 benchmark/proof/hy4_pool.py readings --seeds 1 --plant own_selections

``rehearse`` is ``rehearse_decode_pool.py`` on the tiny cell under
``benchmark/rehearse/decode-pool-hy4/`` (any platform, measures nothing);
``readings`` is ``decode_pool_readings.py`` on ``hy4-preview.decode-pool-32k``
(the chip: the program's mean logit gap and the int8 control's, each through
``harness.judge``; one JSON line a seed, also appended to a file of the
readings).
``--plant`` serves a build with a fault (the reference keeps the
configuration's mathematics): ``own_selections``, the shared layers scoring
their keys with an indexer of their own (matrices drawn as the full layers'
are, on their own input, with index pools of their own), or
``sink_dropped``, every layer's softmax without its sink.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BENCH = os.path.join(ROOT, "benchmark", "rehearse", "decode-pool-hy4", "BENCHMARK.json")
TINY = "hy4-tiny.decode-pool-tiny"
CELL = "hy4-preview.decode-pool-32k"
PLANTS = ("own_selections", "sink_dropped")


def rehearsal():
    """``rehearse_decode_pool`` looking up the tiny Hy4 cell."""
    from benchmark import harness

    mod = harness.load_module("proof", "rehearse_decode_pool")
    mod.BENCH, mod.CELL = BENCH, TINY
    return mod


def own_selections(params, m, seed, std):
    """``params`` with the indexer's leaves stacked over every layer: a shared
    layer is given matrices of its own, drawn normal(0, ``std``) from
    ``seed``, and the norm's gain and offset of the full layer before it."""
    import jax
    import jax.numpy as jnp

    types = m["indexer_types"]
    full = [i for i, kind in enumerate(types) if kind == "full"]
    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    out = dict(params)
    for n, k in enumerate(sorted(k for k in params if k.startswith("index_"))):
        layers = []
        for i, kind in enumerate(types):
            own = params[k][max(j for j, f in enumerate(full) if f <= i)]
            if kind == "shared" and "norm" not in k:
                draw = jax.random.normal(jax.random.fold_in(key, 8 * i + n), own.shape)
                own = (std * draw).astype(own.dtype)
            layers.append(own)
        out[k] = jnp.stack(layers)
    return out


def drop_sinks():
    """``mla_moe`` serving every layer without its sink, for this process."""
    from mxnet_tpu.models import mla_moe

    layer = mla_moe._layer
    mla_moe._layer = lambda params, i, c: {
        k: v for k, v in layer(params, i, c).items() if k != "attn_sink"}


def with_own_selections(run_cls):
    """``run_cls`` serving the program configured with every layer full, the
    shared layers given indexers of their own (:func:`own_selections`); its
    reference keeps the cell's configuration."""

    class OwnSelections(run_cls):
        def setup(self):
            cell = self.model
            self.model = dict(cell, indexer_types=["full"] * len(cell["indexer_types"]))
            try:
                super().setup()
            finally:
                self.model = cell

        def make_params(self):
            served, cell = self.model, self.cell.config["program"]
            if served is cell:
                return super().make_params()
            self.model = cell
            try:
                return own_selections(super().make_params(), cell, self.seed,
                                      self.cell.config["init_std"])
            finally:
                self.model = served

    return OwnSelections


def main(argv):
    if argv[:1] == ["rehearse"]:
        return rehearsal().drive(argv[1:])
    if argv[:1] == ["readings"]:
        from benchmark import harness

        mod = harness.load_module("proof", "decode_pool_readings")
        mod.CELL = CELL
        rest = list(argv[1:])
        if "--plant" in rest:
            at = rest.index("--plant")
            fault = rest[at + 1]
            del rest[at:at + 2]
            if fault not in PLANTS:
                raise SystemExit("--plant: one of %s" % ", ".join(PLANTS))
            if fault == "sink_dropped":
                import mxnet_tpu  # noqa: F401

                drop_sinks()
            else:
                load = harness.load_module

                def planted(kind, name):
                    runner = load(kind, name)
                    if (kind, name) == ("runners", "serve_decode_pool_hy4"):
                        runner.Run = with_own_selections(runner.Run)
                    return runner

                harness.load_module = planted
            rest += ["--no-control"]
        sys.argv = [sys.argv[0]] + rest
        return mod.main()
    raise SystemExit(__doc__)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
