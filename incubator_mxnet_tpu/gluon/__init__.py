"""Gluon: the imperative layer API (ref: python/mxnet/gluon/__init__.py).

Block/HybridBlock with jit hybridization, Parameter/ParameterDict, Trainer,
losses, nn/rnn layers, data pipeline, model zoo — the full Gluon surface of
the reference, TPU-native (see gluon/block.py for the CachedOp design).
"""
from .parameter import Parameter, Constant, ParameterDict, DeferredInitializationError
from .block import Block, HybridBlock, SymbolBlock
from .trainer import Trainer
from . import nn
from . import loss
from . import utils
from . import data
from . import rnn
from . import model_zoo
from . import contrib
