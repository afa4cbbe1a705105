"""Op-builder layer API (reference python/paddle/fluid/layers/)."""
from . import nn
from . import tensor
from . import math_ops
from . import control_flow
from . import rnn  # noqa: F401
from . import detection  # noqa: F401
from . import io
from . import metric_op
from . import learning_rate_scheduler
from . import loss
from . import sequence  # noqa: F401
from . import collective  # noqa: F401
from . import decoder  # noqa: F401

from .nn import *  # noqa: F401,F403
from .tensor import *  # noqa: F401,F403
from .math_ops import *  # noqa: F401,F403
from .control_flow import *  # noqa: F401,F403
from .rnn import *  # noqa: F401,F403
from .io import *  # noqa: F401,F403
from .metric_op import *  # noqa: F401,F403
from .learning_rate_scheduler import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .sequence import *  # noqa: F401,F403
from .ops import *  # noqa: F401,F403
from .detection import *  # noqa: F401,F403
from .collective import *  # noqa: F401,F403
from .decoder import *  # noqa: F401,F403
from .distributions import (  # noqa: F401
    Normal, Uniform, Categorical, MultivariateNormalDiag)

from . import distributions  # noqa: F401
