"""Symbolic model builders (ref: example/image-classification/symbols/).

Each module exposes ``get_symbol(num_classes, ...)`` returning a Symbol
with a ``SoftmaxOutput`` head, matching the reference example zoo that the
Module training scripts consume. The Gluon model zoo lives separately in
``gluon/model_zoo``; the transformer/LLM family (the TPU-native
long-context flagship) in ``transformer.py``.
"""
from . import (  # noqa: F401
    alexnet, inception, lenet, mlp, mobilenet, resnet, resnext, ssd, vgg,
)

_BUILDERS = {
    "mlp": mlp,
    "lenet": lenet,
    "resnet": resnet,
    "resnext": resnext,
    "alexnet": alexnet,
    "vgg": vgg,
    "mobilenet": mobilenet,
    "inception-v3": inception,
    "inception-bn": inception,
    "googlenet": inception,
    "ssd": ssd,
}
_VERSION_KW = {"inception-v3": "v3", "inception-bn": "bn",
               "googlenet": "v1"}


def get_symbol(network, **kwargs):
    """Dispatch like the reference's train scripts:
    ``importlib.import_module('symbols.' + args.network).get_symbol(...)``."""
    if network not in _BUILDERS:
        raise ValueError("unknown network %r; have %s" % (network, sorted(_BUILDERS)))
    if network in _VERSION_KW:
        kwargs.setdefault("version", _VERSION_KW[network])
    return _BUILDERS[network].get_symbol(**kwargs)
