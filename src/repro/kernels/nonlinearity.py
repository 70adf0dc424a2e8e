"""The elementwise moment integrands of the Hyvarinen entropy terms.

``nonlinear_terms`` is the *single* definition of the two integrands
``(log cosh u, u exp(-u^2/2))`` shared by every consumer: the kernel
wrappers (:mod:`repro.kernels.ops`), the entropy measures
(:mod:`repro.core.measures`), and the mesh plan's column moments. It
lives here — not in ``core`` — because the kernels package must stay
free of ``core`` imports while ``core`` freely imports kernels.
"""

from __future__ import annotations

import jax.numpy as jnp


def nonlinear_terms(u):
    """Elementwise ``(log cosh u, u exp(-u^2/2))`` — the two integrands.

    ``log cosh`` is computed in the overflow-safe form
    ``|u| + log1p(exp(-2|u|)) - log 2``, the reference form. Both terms
    are exactly 0 at ``u = 0``, which the padded/masked reduction paths
    (blocked row kernel, sharded column moments, chunked streaming sums)
    rely on: zeroed pad entries contribute nothing to the sums.

    The Pallas kernels compute ``log(1 + exp(-2|u|))`` instead (cheaper
    on a TPU, the same fp32 accuracy) and take log 2 off their sums once
    per sample chunk (see :mod:`repro.kernels.pairwise_stats`), so a
    zeroed pad entry adds only the rounding of log 2 to their sums.
    """
    au = jnp.abs(u)
    logcosh = au + jnp.log1p(jnp.exp(-2.0 * au)) - jnp.log(2.0)
    uexp = u * jnp.exp(-0.5 * u * u)
    return logcosh, uexp
