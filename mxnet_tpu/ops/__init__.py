"""Operator table population.  Importing this package registers every op
family (reference: static registration of NNVM_REGISTER_OP at library load,
SURVEY.md §3.2)."""
from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import random_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import image_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import attention_ops  # noqa: F401
from . import flash_attention  # noqa: F401
from . import qk_norm_rope  # noqa: F401
from . import kda  # noqa: F401
from . import selective_scan  # noqa: F401
from . import quantization_ops  # noqa: F401
from . import legacy_ops  # noqa: F401
from .registry import OP_TABLE, get_op, list_ops, register  # noqa: F401
