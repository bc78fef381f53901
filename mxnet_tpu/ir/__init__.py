"""Graph-level IR pass framework (ISSUE 13, ROADMAP item 1).

A small typed rewrite layer over the existing ``Symbol``/``_Node``
graph — the Relay lesson (arXiv:1810.00952) applied to this repo's
nnvm-style IR: fusion, bind-time constant folding and int8
post-training quantization compose as *passes over one IR* instead of
living as a builder branch, a bespoke predictor split, and nothing.

- :mod:`.match` — the pattern matcher (``Pat``/``match``).
- :mod:`.passes` — ``Pass``/``RulePass``/``PassManager``,
  ``apply_passes`` (pipeline from ``MXNET_IR_PASSES``), ``PassError``.
- :mod:`.rules` — the fusion rules (bottleneck unit, transpose cancel,
  residual-add-into-conv-epilogue) + the rule registry whose declared
  kernels feed the autotuner (``tune.rule_kernels``).
- :mod:`.fold` — the bind-time constant-fold split
  (:class:`~.fold.FoldPlan`), shared by the serving tier and the
  C-predict ABI.
- :mod:`.quantize` — int8 PTQ for the serving path
  (``quantize_for_serving``, ``CalibrationError``).

Every pass records per-rule hits / nodes rewritten / folded and
quantized counts plus calibration gauges into
``profiler.pass_stats`` (``dump_profile``'s ``passStats`` family).
"""
from .match import Match, Pat, match, node_attr  # noqa: F401
from .passes import (  # noqa: F401
    PASSES,
    Pass,
    PassError,
    PassManager,
    RulePass,
    apply_passes,
    splice,
)
from .rules import (  # noqa: F401
    Rule,
    fusion_rules,
    get_rule,
    list_rules,
    register_rule,
    registered_kernels,
    residual_rules,
)
from .fold import FoldPlan  # noqa: F401
from .quantize import (  # noqa: F401
    QUANTIZABLE_OPS,
    CalibrationError,
    QuantizePass,
    calibrate,
    quantize_for_serving,
)
