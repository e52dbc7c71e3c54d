"""Whole-model symbols built from registered operators: what
`gluon.model_zoo` is to Gluon, for `mx.sym` and `Module`."""
from . import granite_hybrid  # noqa: F401
