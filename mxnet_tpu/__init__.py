"""mxnet_tpu — a TPU-native framework with the mxnet 0.9.5 surface.

``import mxnet_tpu as mx`` gives the reference's user API (python/mxnet/
__init__.py): mx.nd, mx.sym, mx.mod, mx.io, mx.kv, mx.metric, mx.init,
mx.optimizer, mx.rnn, mx.mon, mx.viz — built on JAX/XLA/Pallas instead of the
HIP/mshadow/NNVM/ps-lite stack.
"""
from __future__ import annotations

# Multi-process bootstrap MUST precede anything that can initialize the
# XLA backend (jax.distributed.initialize rejects a live backend), the way
# the reference dispatches DMLC_ROLE at import (kvstore_server.py). Cheap
# no-op unless the env declares a multi-process job (DMLC_NUM_WORKER /
# JAX_NUM_PROCESSES > 1).
from . import dist as _dist_bootstrap
_dist_bootstrap.init_from_env()

from .base import MXNetError, __version__
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context
from . import base
from . import engine
from . import random
from . import faults
from . import ops  # registers all operators
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import symbol
from . import symbol as sym
from . import symbol as symbol_doc
from . import executor
from . import io
from . import data
from . import image
from . import recordio
from . import metric
from . import initializer
from . import initializer as init
from . import optimizer
from . import optimizer as opt
from . import precision
from . import lr_scheduler
from . import kvstore as kv
from . import kvstore
from . import model
from . import checkpoint
from . import guardian
from . import module
from . import module as mod
from . import serving
from . import callback
from . import monitor
from . import monitor as mon
from . import profiler
from . import telemetry
from . import visualization
from . import visualization as viz
from . import rnn
from . import attribute
from . import name
from . import test_utils
from . import operator
from . import rtc
from . import torch
from . import plugin
from . import parallel
from . import dist
from . import autopilot
from . import gateway

from .attribute import AttrScope
from .name import NameManager
from .model import FeedForward
from .ndarray import waitall
