"""Pallas TPU kernels — the custom-kernel slot.

Parity: the reference fills this slot with runtime x86 codegen
(operators/jit/, Xbyak: act/blas/lstm/gru/seqpool kernels dispatched from
a kernel pool, jit/README.md). On TPU the same role — hand-written
kernels for ops the compiler doesn't fuse optimally — is filled by
Pallas (pallas_call over VMEM blocks feeding the MXU/VPU).
"""
from . import registry  # noqa: F401
from .flash_attention import flash_attention  # noqa: F401
from .fused_optimizer import bucket_sweep, fused_adam, fused_sgd  # noqa: F401,E501
from .quantized_matmul import quantized_matmul  # noqa: F401
from . import grouped_matmul  # noqa: F401
from . import sparse_index  # noqa: F401
from . import mamba2_ssd  # noqa: F401
from . import selective_scan  # noqa: F401
from . import short_conv  # noqa: F401
