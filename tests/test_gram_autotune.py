"""Gram-kernel tile autotuner: validity, caching, monotonicity vs the fixed
legacy tiles, and end-to-end dispatch with autotuned tiles."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gram import autotune
from repro.kernels.gram import ops as gops
from repro.kernels.gram import ref as gref

# a grid of (N, F) covering square, tall, wide, tiny and the zero-padded
# ragged cases the kernel supports via padding
SHAPE_GRID = [(128, 128), (512, 256), (4096, 192), (25088, 1280),
              (16384, 3072), (300, 100), (257, 129), (100, 300),
              (8192, 12800), (7, 3)]


@pytest.mark.parametrize("n,f", SHAPE_GRID)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_choices_valid(n, f, dtype):
    """Every choice respects TPU tiling (lane 128 / dtype sublane), the
    VMEM budget, and is drawn from the candidate grid."""
    bf, bn = autotune.choose_tiles(n, f, dtype)
    sub = 16 if dtype == "bfloat16" else 8
    assert bf % 128 == 0
    assert bn % sub == 0
    assert bf in autotune.BF_CANDIDATES and bn in autotune.BN_CANDIDATES
    assert autotune.vmem_bytes(bf, bn, dtype) <= autotune.DEFAULT_VMEM_BUDGET


@pytest.mark.parametrize("n,f", SHAPE_GRID)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_never_predicted_slower_than_fixed_defaults(n, f, dtype):
    """(128, 512) is in the candidate set, so the argmin choice can never
    be predicted slower than the fixed legacy tiles."""
    bf, bn = autotune.choose_tiles(n, f, dtype)
    assert autotune.predicted_time(n, f, dtype, bf, bn) <= \
        autotune.predicted_time(n, f, dtype, 128, 512)


def test_choice_cached_per_shape():
    a = autotune.choose_tiles(4096, 768)
    assert autotune.choose_tiles(4096, 768) is a          # lru_cache hit
    assert autotune.choose_tiles(4096, 768, "bfloat16") is not a


def test_vmem_budget_binds():
    """A tight budget must push the choice to smaller tiles, never crash."""
    bf, bn = autotune.choose_tiles(65536, 8192, vmem_budget=2 * 2 ** 20)
    assert autotune.vmem_bytes(bf, bn) <= 2 * 2 ** 20
    big = autotune.choose_tiles(65536, 8192)
    assert autotune.vmem_bytes(*big) > autotune.vmem_bytes(bf, bn)


def test_bf16_streams_deeper_token_tiles():
    """Half the itemsize -> the same VMEM budget holds deeper token tiles
    on large shapes (the bf16-streaming/autotune composition)."""
    bf32, bn32 = autotune.choose_tiles(16384, 3072, "float32")
    bf16, bn16 = autotune.choose_tiles(16384, 3072, "bfloat16")
    assert bn16 >= bn32


@pytest.mark.parametrize("n,f", [(300, 100), (257, 129), (512, 192)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_autotuned_tiles_match_ref(n, f, dtype):
    """bf=bn=None -> autotuned tiles; interpret-mode kernel must still
    match the oracle on ragged shapes in both streaming dtypes."""
    x = jax.random.normal(jax.random.PRNGKey(0), (n, f), dtype)
    a = gops.gram(x, impl="interpret")
    b = gref.gram(x)
    tol = 1e-1 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(a["s2"]), np.asarray(b["s2"]),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(a["s1"]), np.asarray(b["s1"]),
                               rtol=tol, atol=tol)


def test_gram_tiles_env_pin(monkeypatch):
    """REPRO_GRAM_TILES pins the tiles globally (the --gram-tiles CLI
    knob); explicit args still win over the env."""
    seen = {}
    import repro.kernels.gram.ops as ops_mod

    def fake_pallas(x, *, bf, bn, interpret):
        seen["tiles"] = (bf, bn)
        return gref.gram(x)

    monkeypatch.setattr(ops_mod, "_pallas_gram", fake_pallas)
    monkeypatch.setenv("REPRO_GRAM_TILES", "256,1024")
    x = jnp.ones((64, 32))
    gops.gram(x, impl="interpret")
    assert seen["tiles"] == (256, 1024)
    gops.gram(x, impl="interpret", bf=128, bn=512)
    assert seen["tiles"] == (128, 512)


def test_tuning_table_rows():
    rows = autotune.tuning_table()
    assert len(rows) == len(autotune.DEFAULT_SHAPES) * 2
    for r in rows:
        assert r["t_pred"] <= r["t_fixed"]
        assert r["speedup"] >= 1.0
