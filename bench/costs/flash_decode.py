"""Operations and bytes one ``flash_decode`` call needs: one new query per
lane against that lane's valid cache rows. Rows past a lane's length, and
lanes that hold no request, are work the algorithm does not need."""


def cost(rows: int, lanes: int, H: int, Hkv: int, dq: int, dv: int,
         itemsize: int) -> tuple:
    """``rows``: valid cache rows summed over the ``lanes`` decoding.
    Returns (flops, bytes)."""
    flops = 2 * H * rows * (dq + dv)
    nbytes = (rows * Hkv * (dq + dv) + lanes * H * (dq + dv)) * itemsize
    return flops, nbytes
