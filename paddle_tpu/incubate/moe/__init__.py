from .gate import GShardGate, NaiveGate, SwitchGate  # noqa: F401
from .moe_layer import MoELayer  # noqa: F401
from .dropless import DroplessMoE, MOE_PLAN_TALLY  # noqa: F401
