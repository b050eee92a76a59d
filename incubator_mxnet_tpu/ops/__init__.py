"""Operator library: importing this package registers all operators.

Single registry (registry.py) serving eager + symbolic modes — the TPU-native
analogue of the reference's NNVM registry populated by src/operator/*.cc
static initializers (SURVEY §2.2).
"""
from . import registry
from .registry import Operator, get_op, list_ops, register, alias

# registration side effects
from . import math        # noqa: F401  elementwise/broadcast/reduce/dot
from . import tensor      # noqa: F401  shape/indexing/ordering/sequence
from . import nn          # noqa: F401  conv/fc/norm/act/pool/loss-outputs
from . import init_ops    # noqa: F401  zeros/ones/arange/...
from . import random_ops  # noqa: F401  samplers
from . import optimizer_ops  # noqa: F401  fused updates
from . import rnn         # noqa: F401  fused RNN + CTC
from . import vision      # noqa: F401  detection/sampling (SSD/RCNN/STN)
from . import attention   # noqa: F401  flash attention
from . import ssm         # noqa: F401  selective scan (state-space layers)
from . import delta_rule  # noqa: F401  gated delta rule (linear attention)
from . import linalg      # noqa: F401  LAPACK la_op family + FFT/count_sketch
from . import quantization  # noqa: F401  INT8 quantize/dequantize/quantized_*

__all__ = ["Operator", "get_op", "list_ops", "register", "alias"]
