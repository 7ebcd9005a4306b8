"""Operations and bytes one latent ``flash_decode`` call (MLA's absorbed
decode) needs: one absorbed query per head and lane, against that lane's
valid latent rows, each read once as the key (``R + r`` lanes) and its
first ``R`` lanes as the value. Rows past a lane's length, and lanes that
hold no request, are work the algorithm does not need."""


def cost(rows: int, lanes: int, H: int, R: int, r: int,
         itemsize: int) -> tuple:
    """``rows``: valid latent rows summed over the ``lanes`` decoding;
    ``R`` the latent rank, ``r`` the rope dims. Returns (flops, bytes)."""
    C = R + r
    flops = 2 * H * rows * (C + R)
    nbytes = (rows * C + lanes * H * (C + R)) * itemsize
    return flops, nbytes
