"""Block plans of the moment kernels, from the shapes alone
(:mod:`registry <repro.kernels.tune.registry>`)."""

from . import registry  # noqa: F401
