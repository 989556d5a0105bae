"""Operator registry package (nnvm-registry equivalent, SURVEY.md §2.2).

Importing this package registers every operator.  New operator modules must
be imported here to appear in the ``mx.nd`` / ``mx.sym`` namespaces.
"""
from . import registry
from .registry import register, get_op, list_ops, alias
from . import tensor  # noqa: F401  (registers tensor ops)
from . import nn      # noqa: F401  (registers NN ops)
from . import random_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import attention  # noqa: F401  (fused SDPA + contrib transformer)
from . import latent_attention  # noqa: F401  (MLA: expanded + absorbed)
from . import det     # noqa: F401  (roi_align / box_nms / box_iou)
from . import moe     # noqa: F401  (expert-parallel MoE FFN)
from . import ssm     # noqa: F401  (selective scan + causal conv)
from . import delta_rule  # noqa: F401  (gated delta rule, matrix state)
from . import quantization_ops  # noqa: F401  (int8 quantize family)
