"""Analytic (bf, bn) tile autotuner for the gram / gram_cross kernels.

The streaming gram kernel (``repro.kernels.gram.gram``) tiles its grid as
``(F/bf, F/bf, N/bn)`` with the token dimension innermost: every X tile is
read once per output block *row/column*, so total HBM reads are

    bytes_in(bf) = 2 * Np * Fp * (Fp / bf) * itemsize

— larger ``bf`` means fewer passes over X, smaller ``bf`` means less VMEM.
FLOPs are fixed at ``2 * Np * Fp^2`` (Np/Fp = zero-padded dims). This module
picks the (bf, bn) pair minimising the roofline time

    t(bf, bn) = max(flops / peak_flops, bytes / hbm_bw)

over a candidate grid, subject to the VMEM budget (double-buffered input
tiles + fp32 accumulator scratch + output block) and TPU tiling constraints
(lane dim multiple of 128, sublane multiple of 8 fp32 / 16 bf16). Hardware
constants come from ``repro.roofline.analysis.HW`` — the same numbers the
dry-run roofline uses, so kernel tunings and model-level rooflines agree
(see docs/roofline.md).

Choices are cached per (N, F, dtype, budget) — the calibration hot loop
re-resolves tiles every batch, and calibration streams have constant
shapes. Because the fixed legacy default (128, 512) is always in the
candidate set, the autotuned pick is never *predicted* slower than it
(tests/test_gram_autotune.py).

Examples (doctested in CI):

>>> choose_tiles(8192, 4096)                     # big square: go wide
(512, 1024)
>>> choose_tiles(8192, 4096, "bfloat16")         # bf16 halves input traffic
(512, 2048)
>>> choose_tiles(300, 100)                       # ragged small shape: the
(128, 512)
>>> # clamp bn = min(bn, N) = 300 makes deeper tiles pure padding waste
>>> choose_tiles(8192, 4096) is choose_tiles(8192, 4096)   # cached
True
>>> t_auto = predicted_time(8192, 4096, "float32", *choose_tiles(8192, 4096))
>>> t_auto <= predicted_time(8192, 4096, "float32", 128, 512)
True

Run ``python -m repro.kernels.gram.autotune`` for the tuning table over the
canonical calibration shapes.
"""
from __future__ import annotations

import functools
from typing import Iterable, List, Optional, Tuple

import jax.numpy as jnp

from repro.roofline.analysis import HW

# candidate tile grid: bf (feature block) on the 128-lane register width,
# bn (token block) on the fp32/bf16 sublane multiples. (128, 512) — the
# legacy fixed default — must stay in this set so autotuned picks are never
# predicted slower than it.
BF_CANDIDATES = (128, 256, 512)
BN_CANDIDATES = (256, 512, 1024, 2048)

#: VMEM budget for one kernel instance. Physical VMEM is ~16 MiB/core; the
#: margin leaves room for the compiler's own spills and semaphores.
DEFAULT_VMEM_BUDGET = 12 * 2 ** 20

#: fixed cost per grid cell (dispatch + pipeline bubble + accumulator
#: revisit). Total HBM traffic is independent of bn (the fp32 accumulator
#: stays VMEM-resident across the token grid), so this term is what makes
#: deeper token tiles win once VMEM allows them.
CELL_OVERHEAD_S = 5e-7

_LANE = 128
_SUBLANE = {2: 16, 4: 8}        # itemsize -> min sublane multiple


def _round_up(n: int, b: int) -> int:
    return -(-n // b) * b


def _itemsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def _eff(n: int, f: int, bf: int, bn: int) -> Tuple[int, int, int, int]:
    """Effective (clamped) tiles + padded dims, mirroring ``gram.gram``'s
    ``bf = min(bf, F)`` clamp and zero-padding."""
    bf_e, bn_e = min(bf, f), min(bn, n)
    return bf_e, bn_e, _round_up(f, bf_e), _round_up(n, bn_e)


def vmem_bytes(bf: int, bn: int, dtype="float32") -> int:
    """VMEM footprint of one (bf, bn) kernel instance, in bytes.

    Two input tiles (xi, xj) of (bn, bf) in the streaming dtype, double
    buffered by the pipeline; fp32 accumulator scratch (bf, bf) + column-sum
    row; fp32 output block (bf, bf) + (1, bf).

    >>> vmem_bytes(128, 512) == 2 * 2 * 512 * 128 * 4 + 2 * (128 * 128 + 128) * 4
    True
    """
    el = _itemsize(dtype)
    inputs = 2 * 2 * bn * bf * el              # xi + xj, double buffered
    scratch = (bf * bf + bf) * 4               # fp32 accumulator + colsum
    out = (bf * bf + bf) * 4                   # fp32 output block + s1 row
    return inputs + scratch + out


def predicted_time(n: int, f: int, dtype, bf: int, bn: int,
                   hw: HW = HW()) -> float:
    """Roofline-model seconds for one full (N, F) gram at tiles (bf, bn).

    Memory term: every X tile is read once per output block row/column
    (2 * Np * Fp * (Fp/bf) * itemsize input bytes) plus the fp32 output
    write. Compute term: 2 * Np * Fp^2 MACs-as-flops on the MXU; fp32
    inputs run the MXU at half its bf16 rate. A fixed ``CELL_OVERHEAD_S``
    per grid cell rewards deeper tiles. Padding waste (Np, Fp) is charged
    to every term, which is what steers ragged shapes to small tiles.
    """
    el = _itemsize(dtype)
    bf_e, bn_e, fp, np_ = _eff(n, f, bf, bn)
    flops = 2.0 * np_ * fp * fp
    bytes_in = 2.0 * np_ * fp * (fp / bf_e) * el
    bytes_out = (fp * fp + fp) * 4.0
    peak = hw.peak_flops * (2.0 / max(2, el))   # fp32 MXU ~ half bf16 rate
    cells = (fp // bf_e) ** 2 * (np_ // bn_e)
    return max(flops / peak, (bytes_in + bytes_out) / hw.hbm_bw) \
        + cells * CELL_OVERHEAD_S


@functools.lru_cache(maxsize=4096)
def choose_tiles(n: int, f: int, dtype: str = "float32", *,
                 vmem_budget: int = DEFAULT_VMEM_BUDGET,
                 hw: HW = HW()) -> Tuple[int, int]:
    """Pick (bf, bn) for an (N, F) gram: argmin of ``predicted_time`` over
    the candidate grid, subject to ``vmem_bytes <= vmem_budget`` and the
    dtype's tiling constraints. Cached per (N, F, dtype, budget).

    The returned tiles may exceed N/F for small inputs — ``gram.gram``
    clamps with ``min(bf, F)`` / ``min(bn, N)`` and zero-pads, so any
    choice from the candidate grid is shape-safe.

    >>> bf, bn = choose_tiles(4096, 192)        # DeiT-tiny width
    >>> bf % 128 == 0 and bn % 8 == 0
    True
    >>> vmem_bytes(*choose_tiles(100_000, 8192)) <= DEFAULT_VMEM_BUDGET
    True
    """
    el = _itemsize(dtype)
    sub = _SUBLANE.get(el, 8)
    feasible = []
    for bf in BF_CANDIDATES:
        for bn in BN_CANDIDATES:
            if bf % _LANE or bn % sub:
                continue
            if vmem_bytes(bf, bn, dtype) > vmem_budget:
                continue
            feasible.append((predicted_time(n, f, dtype, bf, bn, hw),
                             bf, bn))
    assert feasible, (n, f, dtype, vmem_budget)
    # stable tie-break: prefer smaller VMEM footprint, then the legacy
    # default ordering (bf asc, bn asc) so equal-cost picks are deterministic
    feasible.sort(key=lambda t: (t[0], vmem_bytes(t[1], t[2], dtype),
                                 t[1], t[2]))
    _, bf, bn = feasible[0]
    return bf, bn


# ---------------------------------------------------------------------------
# tuning table (the kernel-side roofline record, see docs/roofline.md)
# ---------------------------------------------------------------------------

#: canonical calibration shapes: (tokens N, width F) for DeiT-Ti/-B/-H MLP
#: hiddens, an LM d_ff, and a ragged zero-padded case.
DEFAULT_SHAPES = ((4096, 192), (4096, 768), (25088, 1280), (16384, 3072),
                  (8192, 12800), (300, 100))


def tuning_table(shapes: Optional[Iterable[Tuple[int, int]]] = None,
                 dtypes: Tuple[str, ...] = ("float32", "bfloat16"),
                 hw: HW = HW()) -> List[dict]:
    """Rows of {n, f, dtype, bf, bn, t_pred, t_fixed, speedup, vmem_kb} for
    each (shape, dtype) — the per-kernel counterpart of the dry-run
    roofline tables (docs/roofline.md)."""
    rows = []
    for n, f in (shapes or DEFAULT_SHAPES):
        for dt in dtypes:
            bf, bn = choose_tiles(n, f, dt, hw=hw)
            t = predicted_time(n, f, dt, bf, bn, hw)
            t_fixed = predicted_time(n, f, dt, 128, 512, hw)
            rows.append({"n": n, "f": f, "dtype": dt, "bf": bf, "bn": bn,
                         "t_pred": t, "t_fixed": t_fixed,
                         "speedup": t_fixed / t,
                         "vmem_kb": vmem_bytes(bf, bn, dt) // 1024})
    return rows


def main() -> int:
    print("n,f,dtype,bf,bn,t_pred_us,t_fixed_us,speedup,vmem_kb")
    for r in tuning_table():
        print(f"{r['n']},{r['f']},{r['dtype']},{r['bf']},{r['bn']},"
              f"{r['t_pred']*1e6:.1f},{r['t_fixed']*1e6:.1f},"
              f"{r['speedup']:.2f}x,{r['vmem_kb']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
