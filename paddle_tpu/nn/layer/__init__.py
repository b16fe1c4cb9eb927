from .layers import Layer, functional_state, functional_call  # noqa: F401
from .common import *  # noqa: F401,F403
from .activation import *  # noqa: F401,F403
from .norm import *  # noqa: F401,F403
from .conv import *  # noqa: F401,F403
from .pooling import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .container import *  # noqa: F401,F403
from .transformer import *  # noqa: F401,F403
from .gated_delta import GatedDeltaNet  # noqa: F401
from .mamba2 import Mamba2Mixer  # noqa: F401
from .rnn import (RNNCellBase, SimpleRNNCell, LSTMCell, GRUCell, RNN,  # noqa: F401
                  BiRNN, SimpleRNN, LSTM, GRU)
