"""Model operations of one image through a ViT, counted from the sizes of a
configuration's ``model`` group."""


def image_flops(m: dict) -> float:
    D, H, dq, dv = m["d_model"], m["n_heads"], m["qk_kept"], m["d_head"]
    F, L, C = m["d_ff_kept"], m["n_layers"], m["n_classes"]
    N = (m["img_size"] // m["patch"]) ** 2
    T, P = N + 1, m["patch"] ** 2 * 3
    proj = D * H * (2 * dq + dv) + H * dv * D + 2 * D * F
    return 2 * (N * P * D + L * (T * proj + H * T * T * (dq + dv)) + D * C)
