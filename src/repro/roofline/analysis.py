"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Three terms per (arch x shape x mesh), in seconds:
    compute    = HLO_FLOPs_per_device / peak_FLOPs
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / link_bw

Sources: ``compiled.cost_analysis()`` for FLOPs/bytes (the SPMD-partitioned
module is the per-device program, so its costs are per-chip);
collective bytes are NOT in cost_analysis — we parse the optimized HLO text
and sum the output bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (wire-byte approximations noted inline).

Hardware constants (TPU v5e per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import logging
import re
from typing import Dict

import jax
import numpy as np

log = logging.getLogger("repro.roofline")


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12      # bf16
    hbm_bw: float = 819e9
    link_bw: float = 50e9           # per ICI link


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# matches e.g.:  %all-reduce.5 = f32[128,1024]{1,0} all-reduce(...)
#                ROOT %r = (bf16[8,16]{...}, f32[4]) all-to-all(...)
_OP_RE = re.compile(
    r"=\s*(\(?[a-z0-9]+\[[^=]*?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


_WARNED_DTYPES: set = set()


def _shape_bytes(shapes_str: str) -> int:
    """Total bytes of every typed shape in an HLO shape string.

    Dtypes missing from ``_DTYPE_BYTES`` (e.g. ``f8e4m3`` on fp8-quantised
    modules) are counted with a conservative 1-byte-per-element floor and
    warned once per dtype — silently dropping them undercounted collective
    traffic for any extended-dtype model.
    """
    total = 0
    for dt, dims in _SHAPE_RE.findall(shapes_str):
        nbytes = _DTYPE_BYTES.get(dt)
        if nbytes is None:
            if dt not in _WARNED_DTYPES:
                _WARNED_DTYPES.add(dt)
                log.warning(
                    "roofline: unknown HLO dtype %r — counting 1 byte/elem "
                    "(add it to _DTYPE_BYTES for exact accounting)", dt)
            nbytes = 1
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * nbytes
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device output bytes of every collective, by op kind.

    'start' variants only (async pairs would double count); 'done' lines
    don't match because their operand is the start tuple.
    """
    out = {k: 0 for k in _COLLECTIVES}
    seen_done = set()
    for m in _OP_RE.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        out[kind] += _shape_bytes(shapes)
    return out


def analyze_compiled(compiled, hw: HW = HW(), *, n_devices: int = 1,
                     logical_flops: float | None = None) -> Dict:
    """Roofline terms from a compiled (SPMD-partitioned) executable.

    XLA's cost_analysis counts while-loop (lax.scan) bodies ONCE, not
    trip_count times (verified empirically) — fatal for scan-over-layers
    models. When ``logical_flops`` (exact jaxpr-level matmul flops, see
    ``jaxpr_matmul_flops``) is provided, the compute term uses it directly
    and the memory/collective terms are scaled by the resulting undercount
    factor (exact when the loop body dominates, which it does for every
    assigned model; raw values are reported alongside).
    """
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    byac = float(cost.get("bytes accessed", 0.0))
    text = compiled.as_text()
    coll = collective_bytes(text)
    coll_total = float(sum(coll.values()))
    factor = 1.0
    if logical_flops is not None and flops > 0:
        factor = max(1.0, (logical_flops / n_devices) / flops)
        flops_corr = logical_flops / n_devices
    else:
        flops_corr = flops
    byac_corr = byac * factor
    coll_corr = coll_total * factor
    terms = {
        "flops_per_device": flops_corr,
        "bytes_per_device": byac_corr,
        "collective_bytes_per_device": coll_corr,
        "collectives": coll,
        "raw_cost_analysis": {"flops": flops, "bytes": byac,
                              "collective_bytes": coll_total},
        "scan_undercount_factor": factor,
        "t_compute": flops_corr / hw.peak_flops,
        "t_memory": byac_corr / hw.hbm_bw,
        "t_collective": coll_corr / hw.link_bw,
    }
    terms["bottleneck"] = max(
        ("compute", "memory", "collective"),
        key=lambda k: terms[f"t_{k}"])
    try:
        mem = compiled.memory_analysis()
        arg_b = int(getattr(mem, "argument_size_in_bytes", 0))
        out_b = int(getattr(mem, "output_size_in_bytes", 0))
        alias_b = int(getattr(mem, "alias_size_in_bytes", 0))
        terms["memory_analysis"] = {
            "argument_bytes": arg_b,
            "output_bytes": out_b,
            "alias_bytes": alias_b,
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(getattr(mem, "temp_size_in_bytes", 0)) + arg_b,
        }
        # analytic lower bound on HBM traffic: every live argument read once
        # + every (non-aliased) output written once. Brackets the HLO-derived
        # upper bound, which on the CPU backend includes bf16->f32 dot-input
        # conversions that the TPU MXU performs in-flight (DESIGN.md §5).
        terms["t_memory_lb"] = (arg_b + out_b - alias_b) / hw.hbm_bw
    except Exception as e:      # noqa: BLE001
        terms["memory_analysis"] = {"error": str(e)}
    return terms


# ---------------------------------------------------------------------------
# exact logical (global) matmul flops from the jaxpr
# ---------------------------------------------------------------------------

def _prod(xs):
    n = 1
    for x in xs:
        n *= int(x)
    return n


def _eqn_flops(eqn) -> float:
    name = eqn.primitive.name
    if name == "dot_general":
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        rhs = eqn.invars[1].aval.shape
        batch = _prod(lhs[d] for d in lb)
        contract = _prod(lhs[d] for d in lc)
        m = _prod(lhs[d] for d in range(len(lhs))
                  if d not in lb and d not in lc)
        n = _prod(rhs[d] for d in range(len(rhs))
                  if d not in rb and d not in rc)
        return 2.0 * batch * m * n * contract
    if name == "conv_general_dilated":
        out = _prod(eqn.outvars[0].aval.shape)
        rhs = eqn.invars[1].aval.shape
        return 2.0 * out * _prod(rhs[:-1])
    return 0.0


def _sub_jaxprs(eqn):
    for key in ("jaxpr", "call_jaxpr", "body_jaxpr", "cond_jaxpr",
                "fun_jaxpr"):
        if key in eqn.params:
            yield eqn.params[key], 1
    if "branches" in eqn.params:
        for br in eqn.params["branches"]:
            yield br, 1


def _count_jaxpr(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            total += eqn.params["length"] * _count_jaxpr(
                eqn.params["jaxpr"].jaxpr)
        elif name == "while":
            total += _count_jaxpr(eqn.params["body_jaxpr"].jaxpr)
        elif name == "cond":
            total += max((_count_jaxpr(br.jaxpr)
                          for br in eqn.params["branches"]), default=0.0)
        else:
            f = _eqn_flops(eqn)
            if f:
                total += f
            else:
                for sub, mult in _sub_jaxprs(eqn):
                    j = getattr(sub, "jaxpr", sub)
                    total += mult * _count_jaxpr(j)
    return total


def jaxpr_matmul_flops(fn, *args) -> float:
    """Exact global matmul/conv flops of fn(*args) — recurses through scan
    with trip counts (the MFU-convention numerator's denominator twin)."""
    closed = jax.make_jaxpr(fn)(*args)
    return _count_jaxpr(closed.jaxpr)


# ---------------------------------------------------------------------------
# model flops (the "useful work" numerator)
# ---------------------------------------------------------------------------

def params_count(cfg) -> Dict[str, float]:
    """Exact parameter counts from the init tree (eval_shape — no alloc)."""
    from repro.models import build_model
    model = build_model(cfg)
    tree = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    total = 0
    active = 0
    E = cfg.moe.num_experts if cfg.moe is not None else 0
    for kp, leaf in flat:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        n = int(np.prod(leaf.shape))
        total += n
        if E and "/mlp/" in path and leaf.ndim >= 3 \
                and E in leaf.shape and "shared" not in path \
                and "router" not in path:
            n = n * cfg.moe.top_k // E
        active += n
    return {"total": float(total), "active": float(active)}


def model_flops(cfg, shape) -> float:
    """6·N·D for train, 2·N·D for forward-only (N = active params,
    D = processed tokens)."""
    pc = params_count(cfg)
    n_act = pc["active"]
    toks = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                 else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_act * toks
