from repro.kernels.expert_gmm import ops, ref
from repro.kernels.expert_gmm.ops import gmm

__all__ = ["ops", "ref", "gmm"]
