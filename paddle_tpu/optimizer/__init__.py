"""paddle_tpu.optimizer (parity: python/paddle/optimizer/)."""
from . import lr  # noqa: F401
from .optimizer import (  # noqa: F401
    Optimizer, SGD, Momentum, Adagrad, RMSProp, Adam, AdamW, Adamax,
    Adadelta, Lamb, Rprop, LBFGS, UPDATE_PLAN_TALLY,
)
