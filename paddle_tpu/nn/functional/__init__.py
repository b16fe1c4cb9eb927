"""paddle_tpu.nn.functional (parity: python/paddle/nn/functional/)."""
from .activation import *  # noqa: F401,F403
from .common import *  # noqa: F401,F403
from .conv import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .norm import *  # noqa: F401,F403
from .pooling import *  # noqa: F401,F403
from .flash_attention import (  # noqa: F401
    flash_attention, scaled_dot_product_attention, flash_attn_unpadded,
    sdp_kernel,
)
from .block_sparse_attention import (  # noqa: F401
    block_sparse_attention, lightning_attention, select_attention_blocks,
)
from ..decode import gather_tree  # noqa: F401
from ...tensor.creation import diag_embed  # noqa: F401
from ...tensor.math import pdist  # noqa: F401
