from .losses import get_loss, LOSSES
from .optimizers import get_optimizer

# imported for their layer-registry side effect: serde's layer_from_config
# must find MultiHeadAttention/LayerNorm/MoEDense/Mamba2Mixer in a FRESH
# process that deserializes a model without having touched these modules
from . import attention as _attention  # noqa: F401
from . import moe as _moe  # noqa: F401
from . import ssm as _ssm  # noqa: F401
