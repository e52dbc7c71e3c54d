"""Operator library package.

Importing this package registers every operator family (the analog of the
reference's static NNVM registration at library load,
`src/operator/*.cc` NNVM_REGISTER_OP).
"""
from .registry import register, get_op, has_op, list_ops, Operator
from .invoke import invoke

# registration side effects
from . import elemwise      # noqa: F401
from . import shape_ops     # noqa: F401
from . import reduce        # noqa: F401
from . import nn            # noqa: F401
from . import random_ops    # noqa: F401
from . import optimizer_ops # noqa: F401
from . import init_ops      # noqa: F401
from . import linalg_ops    # noqa: F401
from . import contrib_ops   # noqa: F401
from . import ssm_ops       # noqa: F401
from . import detection     # noqa: F401
from . import quantization_ops  # noqa: F401
from . import compat_ops    # noqa: F401

__all__ = ["register", "get_op", "has_op", "list_ops", "Operator", "invoke"]
