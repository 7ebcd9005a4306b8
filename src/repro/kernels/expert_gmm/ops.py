"""Grouped matmul for the held-expert layer (``models.mlp.apply_moe``):
rows sorted by expert, each multiplied by its expert's matrix.

The kernel is megablox's ``gmm`` (``jax.experimental.pallas.ops.tpu``),
which visits only the row tiles the groups cover: its work follows the
(token, expert) pairs routed to the held experts, not the pairs a token
could have had. Rows past the last group are not computed and their
values are unspecified; the caller masks them.

impl resolution (env ``REPRO_GMM_IMPL`` overrides): 'pallas' on TPU,
'ref' (``jax.lax.ragged_dot``) elsewhere, 'interpret' for kernel tests.
Every call records ``expert_gmm:<impl>`` in ``kernels.RESOLVED``.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import note_impl, per_device
from repro.kernels.expert_gmm import ref as _ref

TM = 128                     # rows per tile
TKN = 1024                   # most lanes of a K or N tile


def _resolve_impl() -> str:
    impl = os.environ.get("REPRO_GMM_IMPL", "")
    if impl:
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _tile(n: int) -> int:
    """A K or N tile: the whole dim up to TKN lanes, else the largest of
    1024, 512, 256, 128 that divides it."""
    if n <= TKN:
        return n
    return next((t for t in (1024, 512, 256, 128) if n % t == 0), 128)


def gmm(lhs, rhs, group_sizes, *, impl=None):
    """lhs: (M,K) rows sorted by group; rhs: (E,K,N); group_sizes: (E,)
    int32, summing to at most M -> (M,N) in lhs's dtype."""
    impl = note_impl("expert_gmm", impl or _resolve_impl())
    if impl == "ref":
        return _ref.gmm(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import ops as mb
    M, K = lhs.shape
    N = rhs.shape[2]
    pad = -M % TM

    def kern(lhs, rhs, sizes):
        if pad:
            lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        out = mb.backend.gmm(lhs, rhs, sizes,
                             preferred_element_type=lhs.dtype,
                             tiling=(TM, _tile(K), _tile(N)),
                             interpret=(impl == "interpret"))
        return out[:M]

    return per_device(kern, (lhs, rhs, group_sizes),
                      ((None, None), (None, None, None), (None,)),
                      (None, None))
