"""Model operations of a decoder step, counted from the sizes of a
configuration's ``model`` group: every matmul of the layers, attention over
the context, and the output head where logits are made."""


def _dims(m):
    return (m["d_model"], m["n_heads"], m["n_kv_heads"], m["qk_kept"],
            m["d_head"], m["d_ff_kept"], m["n_layers"], m["vocab_size"])


def decode_flops(m: dict, lanes: int, rows: int) -> float:
    """One decode step of ``lanes`` requests attending to ``rows`` cache
    rows between them, each making its logits."""
    D, H, Hkv, dq, dv, F, L, V = _dims(m)
    proj = D * (H * dq + Hkv * dq + Hkv * dv) + H * dv * D + 3 * D * F
    return 2 * L * (lanes * proj + H * rows * (dq + dv)) + 2 * lanes * D * V


def prefill_flops(m: dict, T: int) -> float:
    """A prompt of ``T`` true tokens, logits for the last one only."""
    D, H, Hkv, dq, dv, F, L, V = _dims(m)
    proj = D * (H * dq + Hkv * dq + Hkv * dv) + H * dv * D + 3 * D * F
    attn = H * (T * (T + 1) // 2) * (dq + dv)
    return 2 * L * (T * proj + attn) + 2 * D * V
