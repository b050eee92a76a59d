"""Gluon neural-network layers (ref: python/mxnet/gluon/nn/__init__.py)."""
from ..block import Block, HybridBlock, SymbolBlock
from .basic_layers import *
from .conv_layers import *
from .attention import *
from .ssm import *
from .delta_net import *
