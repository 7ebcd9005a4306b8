"""Operations and bytes one ``flash_attention`` call needs, for ``batch``
sequences of ``T`` true tokens (padding is work the algorithm does not
need), causal or bidirectional."""


def cost(T: int, H: int, Hkv: int, dq: int, dv: int, causal: bool,
         itemsize: int, batch: int = 1) -> tuple:
    """Returns (flops, bytes)."""
    pairs = T * (T + 1) // 2 if causal else T * T
    flops = 2 * batch * H * pairs * (dq + dv)
    nbytes = batch * T * (H * dq + Hkv * dq + Hkv * dv + H * dv) * itemsize
    return flops, nbytes
