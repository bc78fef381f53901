"""Runner for the decode pool of a Hy4-preview deployment:
``serve_decode_pool_sarvam``'s ``Run`` (loaded by name, not edited) with
``mxnet_tpu/models/mla_moe.py`` as the configuration sets it (four
hyper-connected streams, an indexer on the ``full`` layers whose selection the
``shared`` layers reuse, a gated attention with learned sinks, a clamped
SwiGLU, a float32 head), its seeded weights (``benchmark/weights_hy4.py``) and
its plain reference (``benchmark/reference/hy4_lm.py``) in the places of
Sarvam-105B's.

Set-up, the window, the sample and the comparison that decides ``correct``
are the parent's own: the page pool sized in bytes to what the streams hold,
one request through each prefill bucket, all streams prefilled before the
window.  The parent module's private copy is given this module's weights (its
``check_layout`` too), reference and counted names.
"""
from benchmark import harness, weights_hy4
from benchmark.reference import hy4_lm

sarvam = harness.load_module("runners", "serve_decode_pool_sarvam")   # a copy of its own
sarvam.pool.ref = hy4_lm
sarvam.weights_sarvam = weights_hy4
sarvam.COUNTED = ("decode_steps", "slot_steps", "active_slot_steps", "tokens", "prefills",
                  "moe_pairs_held", "moe_tokens", "moe_experts_touched",
                  "moe_pairs_at_max_load", "dsa_keys_scanned", "dsa_keys_selected",
                  "dsa_selections_reused")
pool_pages = sarvam.pool_pages


class Run(sarvam.Run):
    def make_params(self):
        return weights_hy4.params(self.model, self.seed, weights_hy4.draws(self.cell.config))
