"""Pure-jnp oracle for the grouped matmul of the held-expert layer."""
from __future__ import annotations

import jax


def gmm(lhs, rhs, group_sizes):
    """lhs: (M,K) rows sorted by group; rhs: (E,K,N); group_sizes: (E,)
    int32 -> (M,N): row i times the matrix of its group, zero past the
    groups. fp32 accumulation, output in lhs's dtype."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)
