"""Model zoo covering the BASELINE configs (book-model parity)."""
from . import lenet  # noqa: F401
from . import resnet  # noqa: F401
from . import transformer  # noqa: F401
from . import wide_deep  # noqa: F401
from . import decoder_lm  # noqa: F401

from .lenet import lenet_train  # noqa: F401
from .resnet import resnet_train  # noqa: F401
from .transformer import (  # noqa: F401
    TransformerConfig, transformer_base, transformer_big,
    transformer_train,
)
from .wide_deep import ctr_train  # noqa: F401
from .decoder_lm import DecoderLMConfig, decoder_lm_train  # noqa: F401
