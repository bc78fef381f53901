"""Selective rematerialization: a save/recompute pass over the
training graph (ISSUE 19, ROADMAP item 4).

The round-5 ceiling argument (ROADMAP S1) says training is
HBM-bandwidth-bound — the lever is moving fewer bytes, not more FLOPs — yet the one
training-side memory knob, ``TrainStep(remat=True)``, is a global
``jax.checkpoint`` that recomputes *everything* in backward, MXU ops
included, and measurably loses throughput. The selective form is a
decision per graph NODE, not per primitive:

- **save** the outputs of the expensive MXU ops (convolutions, matmuls,
  the Pallas fused units) — recomputing one of these costs real FLOPs
  and a second HBM sweep over its inputs;
- **recompute** the cheap elementwise tails (BN apply, ReLU, pad,
  bias-add, softmax, reshapes) — regenerating them from the saved MXU
  outputs is near-free on spare VPU cycles and saves one full
  activation copy of HBM each.

Lowering uses named checkpointing: the executor's graph closure wraps
each to-save node's outputs in ``jax.ad_checkpoint.checkpoint_name``
(the node NAME is the label) and ``TrainStep(remat="pass")`` wraps the
loss in ``jax.checkpoint`` under
``jax.checkpoint_policies.save_only_these_names`` — a per-site policy,
not a global primitive filter, so two ops lowering to the same
primitive can still make different save/recompute choices. With the
pass off the closure is built without names and behavior is
bit-identical to today.

The decision itself is deliberately a table over op names
(:data:`SAVE_OPS`): like the fusion rules, it states the policy in IR
terms where the pipeline ranker (``tune/pipeline.py``) can price it
against alternatives, instead of burying it in trace-time heuristics.
"""
from __future__ import annotations

from ..base import MXNetError

# Op families whose outputs are SAVED (checkpointed) under the
# selective policy: MXU-bound ops whose recomputation costs a second
# pass over their inputs at real FLOP cost. Everything else — BN
# apply, activations, pad, bias-add, softmax, pooling, reshapes —
# is recomputed in backward from the nearest saved producer.
SAVE_OPS = frozenset((
    "Convolution",
    "Deconvolution",
    "FullyConnected",
    "FusedBottleneckUnit",
    "_ConvResidualAdd",
    "_int8_convolution",
    "_int8_fully_connected",
    "dot",
    "batch_dot",
    "_linalg_gemm",
    "_linalg_gemm2",
    "Correlation",
))


class RematPlan:
    """One graph's save/recompute decision.

    ``save`` / ``recompute`` are tuples of node names (the
    ``checkpoint_name`` labels); a name appearing in ``save`` is
    offered to the executor's closure for wrapping. Duplicated node
    names across the two classes resolve toward *save* at lowering
    time (saving more than planned costs memory, never correctness).
    """

    def __init__(self, save, recompute):
        self.save = tuple(save)
        self.recompute = tuple(recompute)

    @property
    def n_save(self):
        return len(self.save)

    @property
    def n_recompute(self):
        return len(self.recompute)

    def to_dict(self):
        return {"save": list(self.save), "recompute": list(self.recompute),
                "n_save": self.n_save, "n_recompute": self.n_recompute}

    def __repr__(self):
        return ("RematPlan(save=%d, recompute=%d)"
                % (self.n_save, self.n_recompute))


def plan_remat(symbol, save_ops=None, record=True):
    """Classify every computing node of ``symbol`` as save or
    recompute. ``save_ops`` overrides the default :data:`SAVE_OPS`
    table (a policy experiment is a different table, not a different
    pass). Records the site counts into ``profiler.pass_stats`` under
    the ``remat`` pass (``record=False`` for introspection that must
    not skew the acceptance evidence)."""
    ops = SAVE_OPS if save_ops is None else frozenset(save_ops)
    save, recompute = [], []
    for node in symbol._topo():
        if node.is_variable():
            continue
        if not node.name:
            raise MXNetError(
                "plan_remat: unnamed %s node — checkpoint_name labels "
                "are node names, every computing node needs one"
                % node.op.name)
        (save if node.op.name in ops else recompute).append(node.name)
    plan = RematPlan(save, recompute)
    if record:
        from .. import profiler

        profiler.pass_record("remat", remat_saved=plan.n_save,
                             remat_recomputed=plan.n_recompute)
    return plan


def policy_for(plan):
    """The ``jax.checkpoint`` policy lowering a :class:`RematPlan`:
    residuals tagged with a saved node's name are kept, everything
    else is recomputed. An empty save list degenerates to full
    recompute (``remat=True``'s behavior)."""
    import jax

    return jax.checkpoint_policies.save_only_these_names(*plan.save)
