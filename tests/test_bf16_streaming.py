"""bf16 activation streaming (`stats_dtype="bfloat16"`): Sigma tolerance on
ill-conditioned inputs, engine parity, the halved tap bytes, fingerprint
separation, and the zero-sparsity pipeline oracle under the bf16 stream.

The invariant: every statistic ACCUMULATES fp32 regardless of the streaming
dtype — bf16 only rounds each tapped activation once (8-bit mantissa,
~0.4% per entry), so second moments must track the fp32 stream to ~1e-2
relative to their largest entry (the documented tolerance, docs/kernels.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CalibrationEngine, PruneConfig, corp_prune, \
    discover_units
from repro.kernels.gram import ops as gops
from repro.models import build_model
from repro.models import common as model_common

from helpers import batch_for, calib_factory, out_of, tiny_cfg

TOL = 1e-2     # documented bf16-stream Sigma tolerance (max-entry relative)


def _relerr(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(a)), 1e-30))


# ---------------------------------------------------------------------------
# kernel-level tolerance on ill-conditioned inputs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scale_span", [1.0, 1e3, 1e6])
def test_gram_bf16_sigma_tolerance_ill_conditioned(scale_span):
    """Columns spanning `scale_span` in magnitude plus a common-mode offset
    — the conditioning regime where a *fp16* stream would overflow and a
    low-precision ACCUMULATOR would lose the small columns entirely. The
    bf16 stream with fp32 accumulation must stay within TOL of fp32."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    n, f = 2048, 96
    scales = jnp.logspace(0, np.log10(scale_span), f)
    x = jax.random.normal(k1, (n, f)) * scales + 0.5 * scales
    g32 = gops.gram(x, impl="ref")
    g16 = gops.gram(x.astype(jnp.bfloat16), impl="ref")
    assert _relerr(g32["s2"], g16["s2"]) <= TOL
    assert _relerr(g32["s1"], g16["s1"]) <= TOL
    # conditioning itself must survive the rounding: the bf16-stream Sigma
    # stays PSD to fp32 tolerance (eigengaps above -TOL * ||Sigma||)
    evs = np.linalg.eigvalsh(np.asarray(g16["s2"], np.float64))
    assert evs.min() > -TOL * np.abs(evs).max()


def test_gram_bf16_interpret_kernel_accumulates_fp32():
    """The Pallas kernel path (interpret mode) on a bf16 input must match
    the fp32-accumulating reference on the SAME rounded input — i.e. the
    kernel's VMEM accumulator is fp32, not bf16."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1024, 64),
                          jnp.bfloat16)
    a = gops.gram(x, impl="interpret")
    b = gops.gram(x, impl="ref")
    np.testing.assert_allclose(np.asarray(a["s2"]), np.asarray(b["s2"]),
                               rtol=1e-5, atol=1e-5)
    assert a["s2"].dtype == jnp.float32


# ---------------------------------------------------------------------------
# tap dtype context
# ---------------------------------------------------------------------------

def test_tap_dtype_context_scopes_and_restores():
    taps = {}
    x = jnp.ones((4, 4))
    model_common.tap(taps, "a", x)
    with model_common.tap_dtype(jnp.bfloat16):
        model_common.tap(taps, "b", x)
        with model_common.tap_dtype(jnp.float32):
            model_common.tap(taps, "c", x)
        model_common.tap(taps, "d", x)
    model_common.tap(taps, "e", x)
    assert taps["a"].dtype == taps["c"].dtype == taps["e"].dtype \
        == jnp.float32
    assert taps["b"].dtype == taps["d"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# engine parity + fingerprints
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["deit-base", "granite-8b"])
def test_engine_bf16_stream_parity(arch):
    """Full pass-1 statistics under the bf16 stream stay within TOL of the
    fp32 stream for every unit (dense moments AND attention energies);
    sample counts are exact."""
    cfg = tiny_cfg(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    calib = calib_factory(cfg, n=3)
    units = discover_units(cfg)
    s32 = CalibrationEngine(model, units, phase=1).run(params, calib())
    s16 = CalibrationEngine(model, units, phase=1,
                            stats_dtype="bfloat16").run(params, calib())
    for u in units:
        for key, a in s32[u.name].items():
            b = s16[u.name][key]
            if key == "n":
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            elif key == "na":
                # activity counts flip only for |x| straddling eps: allow
                # a sliver of the token count
                tol_na = 0.02 * float(np.max(np.asarray(s32[u.name]["n"])))
                assert np.max(np.abs(np.asarray(a) - np.asarray(b))) \
                    <= max(tol_na, 1.0), (u.name, key)
            else:
                assert _relerr(a, b) <= 2 * TOL, (u.name, key)
        if "s2" in s32[u.name]:     # Sigma itself: the documented TOL
            assert _relerr(s32[u.name]["s2"], s16[u.name]["s2"]) <= TOL, \
                u.name


@pytest.mark.parametrize("arch", ["deit-base", "granite-8b",
                                  "qwen3-moe-235b-a22b"])
def test_bf16_stream_halves_tap_bytes(arch):
    """What the bf16 stream is for: one calibration batch's activation
    taps take at least 1.9x fewer bytes than under the fp32 stream (~2x;
    an MoE's routing mask stays fp32)."""
    cfg = tiny_cfg(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = next(iter(calib_factory(cfg, n=1)()))

    def tap_bytes(dtype):
        taps = {}
        with model_common.tap_dtype(dtype):
            model.apply(params, batch, taps=taps)
        return sum(t.size * t.dtype.itemsize for t in jax.tree.leaves(taps))

    assert tap_bytes(jnp.float32) / tap_bytes(jnp.bfloat16) >= 1.9


def test_engine_fingerprint_includes_stats_dtype():
    cfg = tiny_cfg("deit-base")
    model = build_model(cfg)
    units = discover_units(cfg)
    e32 = CalibrationEngine(model, units, phase=1)
    e16 = CalibrationEngine(model, units, phase=1, stats_dtype="bfloat16")
    assert e32.fingerprint != e16.fingerprint


# ---------------------------------------------------------------------------
# pipeline oracles
# ---------------------------------------------------------------------------

def test_zero_sparsity_oracle_under_bf16_stream():
    """corp_prune at 0/0 sparsity with stats_dtype=bfloat16: statistics are
    gathered (in bf16) but nothing is pruned, so params must pass through
    bitwise identical — the streaming dtype can never touch the weights."""
    cfg = tiny_cfg("deit-base")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    new_p, new_c, _ = corp_prune(model, params, calib_factory(cfg, n=2),
                                 PruneConfig(0.0, 0.0),
                                 stats_dtype="bfloat16")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    y0 = out_of(model, params, batch_for(cfg))
    y1 = out_of(build_model(new_c), new_p, batch_for(cfg))
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


def test_prune_under_bf16_stream_end_to_end():
    """The full 50/50 pipeline under the bf16 stream produces a working
    smaller model with finite outputs and sane compensation diagnostics."""
    cfg = tiny_cfg("deit-base")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    new_p, new_c, report = corp_prune(model, params, calib_factory(cfg),
                                      PruneConfig(0.5, 0.5),
                                      stats_dtype="bfloat16")
    y = out_of(build_model(new_c), new_p, batch_for(cfg))
    assert np.all(np.isfinite(np.asarray(y, np.float32)))
    for name, d in report["units"].items():
        assert np.all(np.asarray(d["j_star"]) <= np.asarray(d["j_uncomp"])
                      * (1 + 1e-3) + 1e-6), name
