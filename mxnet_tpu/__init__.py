"""mxnet_tpu — a TPU-native deep learning framework with the MXNet surface.

Brand-new implementation on JAX/XLA (see SURVEY.md at repo root): NDArray
imperative layer + autograd, Symbol graph API + one-XLA-module executor,
Module and Gluon front ends, KVStore data-parallel training over device
meshes, and the reference's operator/IO/optimizer/metric surfaces.

Import convention mirrors the reference: ``import mxnet_tpu as mx``.
"""

__version__ = "0.1.0"

from . import base  # noqa: F401
from .base import MXNetError  # noqa: F401
from .context import Context, cpu, cpu_pinned, current_context, gpu, num_gpus, num_tpus, tpu  # noqa: F401
from . import context  # noqa: F401

# before anything can compile (context.compile_cache_dir says why)
context.compile_cache_dir()

from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from . import symbol  # noqa: F401
from . import symbol as sym  # noqa: F401
from . import autograd  # noqa: F401
from . import random  # noqa: F401
from . import random as rnd  # noqa: F401
from .executor import Executor  # noqa: F401

from . import initializer  # noqa: F401
from .initializer import init  # noqa: F401
from . import optimizer  # noqa: F401
from . import optimizer as opt  # noqa: F401
from . import lr_scheduler  # noqa: F401
from . import metric  # noqa: F401
from . import callback  # noqa: F401
from . import monitor  # noqa: F401
from . import monitor as mon  # noqa: F401
from . import io  # noqa: F401
from . import recordio  # noqa: F401
from . import kvstore  # noqa: F401
from . import kvstore as kv  # noqa: F401
from . import model  # noqa: F401
from . import module  # noqa: F401
from . import module as mod  # noqa: F401
from . import gluon  # noqa: F401
from . import operator  # noqa: F401
from . import config  # noqa: F401
from . import embedding  # noqa: F401
from . import ir  # noqa: F401
from . import contrib  # noqa: F401
from . import name  # noqa: F401
from . import attribute  # noqa: F401
from .attribute import AttrScope  # noqa: F401
from . import visualization  # noqa: F401
from . import visualization as viz  # noqa: F401
from . import rtc  # noqa: F401
from . import log  # noqa: F401
from . import libinfo  # noqa: F401
from . import executor_manager  # noqa: F401
from . import storage  # noqa: F401
from . import profiler  # noqa: F401
from . import engine  # noqa: F401
from . import dist  # noqa: F401
from . import tracker  # noqa: F401
from . import chaos  # noqa: F401
from . import serving  # noqa: F401
from . import checkpoint  # noqa: F401
from .checkpoint import CheckpointManager  # noqa: F401
from . import test_utils  # noqa: F401

from .model import load_checkpoint, save_checkpoint  # noqa: F401
from .util import is_np_array  # noqa: F401
