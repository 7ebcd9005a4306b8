"""Operations and bytes the held experts' grouped matmuls need in decode
steps: each (token, held expert) pair of a lane that holds a request
through the expert's gate, up and down projections, and each expert that
got such a pair read once."""


def cost(pairs: float, experts_hit: float, D: int, F: int,
         itemsize: int) -> tuple:
    """``pairs`` and ``experts_hit`` summed over the layers and steps
    counted; ``D`` the model width, ``F`` the expert width. Returns
    (flops, bytes): weights of the experts hit, and per pair its row in
    and out of the gate/up and down matmuls."""
    flops = 2 * pairs * 3 * D * F
    nbytes = (experts_hit * 3 * D * F + pairs * 3 * (D + F)) * itemsize
    return flops, nbytes
