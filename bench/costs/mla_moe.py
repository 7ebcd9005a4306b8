"""Model operations of an MLA + MoE decoder (DeepSeek-V3), counted from
the sizes of a configuration's ``model`` group: every matmul of the latent
attention, the dense and shared-expert MLPs, the router, the routed
(token, held expert) pairs, and the output head. A decode step runs the
attention absorbed (q_nope·W_uk, attention over the latent rows, W_uv), a
prefill as published (keys and values lifted from the latent, causal
attention over the prompt)."""


def decode_flops(m: dict, lanes: int, rows: int, pairs: float) -> float:
    """One decode step of ``lanes`` requests attending to ``rows`` latent
    rows between them, with ``pairs`` (token, held expert) pairs over its
    MoE layers, each request making its logits."""
    D, H, L, V = m["d_model"], m["n_heads"], m["n_layers"], m["vocab_size"]
    a, mo = m["mla"], m["moe"]
    Rq, R, r, dv = (a["q_lora_rank"], a["kv_lora_rank"], a["qk_rope_dim"],
                    a["v_dim"])
    dq, F, Fd = m["qk_kept"], m["d_ff_kept"], m["dense_d_ff_kept"]
    nd = m["first_k_dense"]
    attn = (D * Rq + Rq * H * (dq + r) + D * (R + r) + H * dq * R
            + H * R * dv + H * dv * D)
    per_lane = L * attn + nd * 3 * D * Fd + (L - nd) * (
        D * mo["num_experts"] + mo["num_shared"] * 3 * D * F) + D * V
    latent = L * H * rows * (2 * R + r)
    return 2 * (lanes * per_lane + latent + pairs * 3 * D * F)


def prefill_flops(m: dict, T: int, pairs: float) -> float:
    """A prompt of ``T`` true tokens with ``pairs`` (token, held expert)
    pairs over its MoE layers, logits for the last token only."""
    D, H, L, V = m["d_model"], m["n_heads"], m["n_layers"], m["vocab_size"]
    a, mo = m["mla"], m["moe"]
    Rq, R, r, dv = (a["q_lora_rank"], a["kv_lora_rank"], a["qk_rope_dim"],
                    a["v_dim"])
    dq, F, Fd = m["qk_kept"], m["d_ff_kept"], m["dense_d_ff_kept"]
    nd = m["first_k_dense"]
    proj = (D * Rq + Rq * H * (dq + r) + D * (R + r) + R * H * (dq + dv)
            + H * dv * D)
    per_token = L * proj + nd * 3 * D * Fd + (L - nd) * (
        D * mo["num_experts"] + mo["num_shared"] * 3 * D * F)
    attn = L * H * (T * (T + 1) // 2) * (dq + r + dv)
    return 2 * (T * per_token + attn + pairs * 3 * D * F + D * V)
