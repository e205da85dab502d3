"""paddle_tpu.nn — neural network layers (paddle.nn parity).

Reference surface: python/paddle/nn/ (19.5k LoC of Layer classes).  See
layer_base.py for the TPU-native Layer/autodiff design.
"""
from .layer_base import (  # noqa: F401
    Layer,
    Parameter,
    Buffer,
    abstract_parameters,
    functional_call,
    current_rng_key,
    rng_scope,
)
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .initializer import ParamAttr  # noqa: F401

from . import recompute as _recompute_mod  # noqa: F401
from .recompute import apply_recompute, mark_recompute, recompute  # noqa: F401

from .common import *  # noqa: F401,F403
from .conv import *  # noqa: F401,F403
from .norm import *  # noqa: F401,F403
from .pooling import *  # noqa: F401,F403
from .activation import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .container import *  # noqa: F401,F403
from .rnn import *  # noqa: F401,F403
from .transformer import *  # noqa: F401,F403
from .decode import *  # noqa: F401,F403
from ..optimizer.clip import (  # noqa: F401  (paddle.nn re-exports clips)
    ClipGradByValue,
    ClipGradByNorm,
    ClipGradByGlobalNorm,
)
from . import utils  # noqa: F401
from . import utils as weight_norm_hook  # noqa: F401  (ref nn/__init__.py:22)
from .utils import weight_norm, remove_weight_norm  # noqa: F401
from .functional import extension  # noqa: F401  (ref nn/__init__.py:19)
from . import vision  # noqa: F401  (ref nn/__init__.py:160 layer.vision)


from ..tensor.math import clip  # noqa: F401  (ref: nn/clip.py:38 re-export)


def clip_by_norm(x, max_norm, name=None):
    """L2-norm clip: ``x·max_norm/max(‖x‖, max_norm)`` (ref: nn/clip.py:39
    ← fluid/layers/nn.py:12375 over operators/clip_by_norm_op.h)."""
    import jax.numpy as jnp

    x = jnp.asarray(x)
    norm = jnp.sqrt(jnp.sum(x * x))
    return x * (max_norm / jnp.maximum(norm, max_norm))
