"""Operator library — importing this package registers every op.

Layout mirrors the reference src/operator/ families:
elemwise/broadcast/matrix -> tensor/*; nn/conv -> the legacy layer ops;
optimizer_ops -> optimizer_op.cc; sample -> sample_op.h; rnn -> cuDNN RNN
replaced with lax.scan.
"""
from . import elemwise  # noqa: F401
from . import broadcast  # noqa: F401
from . import matrix  # noqa: F401
from . import init_ops  # noqa: F401
from . import sample  # noqa: F401
from . import nn  # noqa: F401
from . import conv  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import rnn_op  # noqa: F401
from . import contrib  # noqa: F401
from . import detection  # noqa: F401
from . import sequence_loss  # noqa: F401
from . import parallel_ops  # noqa: F401
from . import transformer  # noqa: F401
from . import ssm  # noqa: F401
from .. import operator  # noqa: F401  (registers the Custom op)
