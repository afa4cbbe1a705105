"""Operator library: JAX/XLA lowerings for every registered op.

Importing this package registers all ops (the analog of the reference's
static REGISTER_OPERATOR initializers, /root/reference/paddle/fluid/
operators/). Submodules are grouped the way the reference groups operator
directories.
"""
from . import activations  # noqa: F401
from . import elementwise  # noqa: F401
from . import matmul  # noqa: F401
from . import basic  # noqa: F401
from . import reduce  # noqa: F401
from . import nn  # noqa: F401
from . import conv  # noqa: F401
from . import random_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import metrics  # noqa: F401
from . import control_flow  # noqa: F401
from . import sequence  # noqa: F401
from . import fused  # noqa: F401
from . import decoder  # noqa: F401
from . import collective  # noqa: F401
from . import distributed_ops  # noqa: F401
from . import rnn  # noqa: F401
from . import beam_search  # noqa: F401
from . import nlp  # noqa: F401
from . import quantize  # noqa: F401
from . import detection  # noqa: F401
from . import misc  # noqa: F401
from . import reader_ops  # noqa: F401
