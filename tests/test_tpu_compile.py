"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers a kernel for a described (not attached)
v5e:2x2 topology and compiles it with the TPU compiler, which refuses what
interpret mode accepts (blocks that break the (8, 128) tiling rule, kernels
that overflow VMEM, kernels left to the automatic partitioner). Each test
asserts the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import kernel_mesh
from repro.kernels.flash_attention import ops as fops
from repro.kernels.flash_decode import ops as dops
from repro.kernels.gram import ops as gops
from repro.kernels.wkv6 import ops as wops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gram_deit_base(one_chip, dtype):
    """CORP's calibration second moment at deit-base's MLP tap: 8 images x
    197 tokens by d_ff 3072."""
    x = sds((1576, 3072), dtype, one_chip)
    assert "tpu_custom_call" in compiled_text(
        lambda x: gops.gram(x, impl="pallas"), x)


def test_gram_cross_shard_local(one_chip):
    """One shard's (N, F) x (N, F/4) slab of the model-sharded gram."""
    x = sds((1576, 3072), "float32", one_chip)
    y = sds((1576, 768), "float32", one_chip)
    assert "tpu_custom_call" in compiled_text(
        lambda x, y: gops.gram_cross(x, y, impl="pallas"), x, y)


@pytest.mark.parametrize("dq", [64, 32])
def test_flash_attention_deit_base(one_chip, dq):
    """deit-base attention (197 tokens, 12 heads), dense and CORP-pruned
    qk (dq 32): the whole-sequence path, one kernel whose blocks hold all
    197 tokens, so nothing pads q/k/v."""
    q = sds((8, 197, 12, dq), "float32", one_chip)
    v = sds((8, 197, 12, 64), "float32", one_chip)
    text = compiled_text(
        lambda q, k, v: fops.attention(q, k, v, causal=False, impl="pallas"),
        q, q, v)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert not [op for op in _instructions(text) if op[3] == "pad"]


def test_vit_attention_reads_the_projections_in_place(one_chip):
    """deit-base-corp50's attention half at a batch of 8: q/k/v leave
    their projections in the layout the whole-sequence kernel reads and
    its output feeds the output projection, with no pad, transpose or
    copy of them in between."""
    import json
    from repro.configs.base import ModelConfig
    from repro.models import attention
    m = json.load(open(DEIT_BASE_CORP50))["model"]
    cfg = ModelConfig(**m)
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: attention.init_attn(jax.random.PRNGKey(0),
                                                   cfg, "attn")))
    x = sds((8, 197, cfg.d_model), "float32", one_chip)

    def attn(p, x):
        return attention.apply_attn(p, x, cfg, "attn", positions=None,
                                    mask_kind="full")[0]

    was = os.environ.get("REPRO_ATTN_IMPL")
    os.environ["REPRO_ATTN_IMPL"] = "pallas"
    try:
        text = compiled_text(attn, params, x)
    finally:
        if was is None:
            del os.environ["REPRO_ATTN_IMPL"]
        else:
            os.environ["REPRO_ATTN_IMPL"] = was
    ops = {op[1]: op for op in _instructions(text)}
    (kern,) = [op for op in ops.values() if "tpu_custom_call" in op[5]]

    def matmul(op):                      # a dot, alone or as a fusion's root
        return op[3] == "convolution" or "kind=kOutput" in op[5]

    feeds = [ops[name] for name in kern[4]]
    users = [op for op in ops.values() if kern[1] in op[4]]
    assert len(feeds) == 3 and all(map(matmul, feeds)), feeds
    assert users and all(map(matmul, users)), users


def test_flash_attention_causal_unaligned(one_chip):
    """Causal GQA prefill at 3000 tokens: one whole-length block overflowed
    VMEM; padding to 128-row blocks fits."""
    q = sds((1, 3000, 12, 128), "bfloat16", one_chip)
    kv = sds((1, 3000, 2, 128), "bfloat16", one_chip)
    assert "tpu_custom_call" in compiled_text(
        lambda q, k, v: fops.attention(q, k, v, causal=True, impl="pallas"),
        q, kv, kv)


def test_flash_decode_qwen2(one_chip):
    """qwen2-1.5b decode over a 1024-row slot cache (12 q / 2 kv heads)."""
    q = sds((8, 12, 128), "bfloat16", one_chip)
    kv = sds((8, 1024, 2, 128), "bfloat16", one_chip)
    valid = sds((8, 1024), "bool", one_chip)
    assert "tpu_custom_call" in compiled_text(
        lambda q, k, v, m: dops.decode_attention(q, k, v, m, impl="pallas"),
        q, kv, kv, valid)


# --- the served decode step at the chat cell's size -------------------------

DEIT_BASE_CORP50 = os.path.join(os.path.dirname(__file__), "..", "bench",
                                "configs", "deit-base-corp50.json")
QWEN2_CORP50 = os.path.join(os.path.dirname(__file__), "..", "bench",
                            "configs", "qwen2-1.5b-corp50.json")
SLOTS, MAX_LEN = 64, 4096
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}
# outputs that hold no new buffer, or are the loop itself
_VIEWS = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}


def _largest_array(shape: str) -> int:
    """Bytes of the largest array in an HLO shape (an array or a tuple)."""
    import re
    best = 0
    for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", shape):
        n = _BYTES.get(dt, 4)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        best = max(best, n)
    return best


def _instructions(text: str):
    """(computation, name, shape, opcode, operand names, line) of every
    instruction of an HLO module's text."""
    import re
    comp = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)$", line)
        if not m or comp is None:
            continue
        rest = m.group(2)
        if rest.startswith("("):               # tuple shape
            depth = 0
            for i, ch in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            shape, rest = rest[:i + 1], rest[i + 2:]
        else:
            shape, _, rest = rest.partition(" ")
        op = re.match(r"([\w\-]+)\((.*?)\)", rest)
        if op:
            yield (comp, m.group(1), shape, op.group(1),
                   re.findall(r"%([\w.\-]+)", op.group(2)), line)


def cache_sized_buffers(text: str, limit: int) -> list:
    """Instructions outside fused computations whose output holds ``limit``
    bytes or more, other than views of the parameters, the loop, and
    in-place writes whose every other operand is smaller than ``limit``
    (one row per lane written into the stack). Each is a copy, slice,
    transpose or allocation of that size."""
    import re
    inst = list(_instructions(text))
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    size = {(c, n): _largest_array(sh) for c, n, sh, *_ in inst}
    writes = {"scatter", "dynamic-update-slice"}
    writing = {c for c, _, _, op, *_ in inst if op in writes}
    bad = []
    for comp, name, shape, op, args, line in inst:
        if comp in fused or op in _VIEWS or size[(comp, name)] < limit:
            continue
        others = sorted(size.get((comp, a), 0) for a in args)[:-1]
        calls = re.search(r"calls=%([\w.\-]+)", line)
        write = op in writes or (op == "fusion" and calls
                                 and calls.group(1) in writing)
        if write and all(b < limit for b in others):
            continue
        bad.append(line.strip()[:200])
    return bad


@pytest.fixture(scope="module")
def qwen2_decode(topo):
    """lm_decode_step of qwen2-1.5b-corp50 over 64 slots x 4096 rows, its
    cache donated as the engine donates it, lowered with the Pallas kernels
    and compiled for one chip of the described v5e. Returns (config, the
    compiled program, what the trace recorded in ``kernels.RESOLVED``)."""
    import json
    from repro import kernels
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    from repro.models.lm import init_lm_cache
    one = SingleDeviceSharding(topo.devices[0])
    cfg = ModelConfig(**json.load(open(QWEN2_CORP50))["model"])
    model = build_model(cfg)

    def placed(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype, one), tree)

    params = placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = placed(jax.eval_shape(
        lambda: init_lm_cache(cfg, SLOTS, MAX_LEN)))
    tok = sds((SLOTS, 1), "int32", one)

    def step(params, tok, cache):
        logits, cache = model.decode_step(params, tok, cache)
        return jnp.argmax(logits[:, -1, :cfg.vocab_size], -1), cache

    before = kernels.RESOLVED.copy()
    was = os.environ.get("REPRO_DECODE_IMPL")
    os.environ["REPRO_DECODE_IMPL"] = "pallas"
    try:
        lowered = jax.jit(step, donate_argnums=(2,)).lower(params, tok, cache)
    finally:
        if was is None:
            del os.environ["REPRO_DECODE_IMPL"]
        else:
            os.environ["REPRO_DECODE_IMPL"] = was
    return cfg, lowered.compile(), kernels.RESOLVED - before


def test_decode_step_never_copies_the_cache(qwen2_decode):
    """The served decode step writes one row per lane per layer into the
    donated stack and ``flash_decode`` reads the stack where it lies: no
    instruction makes a buffer of one layer's K (64 x 4096 x 128 bf16) or
    more — no copy, slice, transpose or allocation of the cache — and the
    program's temporaries stay under one layer's K and V."""
    cfg, compiled, _ = qwen2_decode
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    k_layer = SLOTS * MAX_LEN * cfg.n_kv_heads * cfg.eff_qk * 2
    v_layer = SLOTS * MAX_LEN * cfg.n_kv_heads * cfg.d_head * 2
    assert cache_sized_buffers(text, k_layer) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < k_layer + v_layer, mem
    # the whole cache is aliased from the donated input to the output
    assert mem.alias_size_in_bytes >= cfg.n_layers * (k_layer + v_layer)


def test_decode_step_reads_every_layer_in_place(qwen2_decode):
    """The trace records, layer by layer, that the kernel read the stacked
    cache in place: 28 of 28 for qwen2-1.5b-corp50, none sliced."""
    cfg, _, took = qwen2_decode
    assert took[("decode_cache", "in_place")] == cfg.n_layers == 28, took
    assert took[("decode_cache", "sliced")] == 0, took
    assert took[("flash_decode", "pallas")] >= 1, took


@pytest.mark.parametrize("T", [1, 512])
def test_wkv6_rwkv6_3b(one_chip, T):
    """rwkv6-3b's recurrence (40 heads of 64): decode and a 512-token
    prefill in chunks of 64."""
    x = sds((1, T, 40, 64), "bfloat16", one_chip)
    u = sds((40, 64), "float32", one_chip)
    assert "tpu_custom_call" in compiled_text(
        lambda r, k, v, w, u: wops.wkv6(r, k, v, w, u, impl="pallas"),
        x, x, x, x, u)


def test_kernels_per_device_on_four_chips(mesh):
    """Inside a program that spans four chips (deepseek-7b decode and
    prefill, 32 heads split over the model axis) the kernels only compile
    as per-device programs: ``kernel_mesh`` wraps them in a shard_map."""
    heads = NamedSharding(mesh, P(None, None, "model", None))
    q = sds((4, 32, 128), "bfloat16", NamedSharding(mesh, P(None, "model")))
    kv = sds((4, 1024, 32, 128), "bfloat16", heads)
    valid = sds((4, 1024), "bool", NamedSharding(mesh, P()))
    seq = sds((1, 200, 32, 128), "bfloat16", heads)

    def step(q, k, v, m, s):
        with kernel_mesh(mesh):
            return (dops.decode_attention(q, k, v, m, impl="pallas"),
                    fops.attention(s, s, s, causal=True, impl="pallas"))
    assert "tpu_custom_call" in compiled_text(step, q, kv, kv, valid, seq)


def test_whole_sequence_attention_per_device_on_four_chips(mesh):
    """deit-base-corp50 attention in a program over four chips: the 12
    heads split over the model axis, and each chip's whole-sequence
    kernel folds its 3 heads into lanes (96 of q/k, 192 of v)."""
    heads = NamedSharding(mesh, P(None, None, "model", None))
    qk = sds((8, 197, 12, 32), "float32", heads)
    v = sds((8, 197, 12, 64), "float32", heads)

    def step(q, k, v):
        with kernel_mesh(mesh):
            return fops.attention(q, k, v, causal=False, impl="pallas")
    assert "tpu_custom_call" in compiled_text(step, qk, qk, v)


# --- the served decode step at the reasoning cell's size ---------------------

DEEPSEEK_V3_CORP50 = os.path.join(os.path.dirname(__file__), "..", "bench",
                                  "configs", "deepseek-v3-corp50.json")
MOE_SLOTS = 256


@pytest.fixture(scope="module")
def deepseek_decode(topo):
    """lm_decode_step of deepseek-v3-corp50 (1 dense + 4 MoE layers, 8 of
    256 experts held) over 256 slots x 4096 latent rows, its cache donated
    as the engine donates it, lowered with the Pallas kernels and compiled
    for one chip of the described v5e. Returns (config, the compiled
    program, what the trace recorded in ``kernels.RESOLVED``)."""
    import json
    from repro import kernels
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    from repro.models.lm import init_lm_cache
    one = SingleDeviceSharding(topo.devices[0])
    cfg = ModelConfig(**json.load(open(DEEPSEEK_V3_CORP50))["model"])
    model = build_model(cfg)

    def placed(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype, one), tree)

    params = placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = placed(jax.eval_shape(
        lambda: init_lm_cache(cfg, MOE_SLOTS, MAX_LEN)))
    tok = sds((MOE_SLOTS, 1), "int32", one)
    lanes = sds((MOE_SLOTS,), "bool", one)

    def step(params, tok, cache, lanes):
        logits, cache, counts = model.decode_step(params, tok, cache,
                                                  count_lanes=lanes)
        return jnp.argmax(logits[:, -1, :cfg.vocab_size], -1), counts, cache

    before = kernels.RESOLVED.copy()
    env = {"REPRO_DECODE_IMPL": "pallas", "REPRO_GMM_IMPL": "pallas"}
    was = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        lowered = jax.jit(step, donate_argnums=(2,)).lower(params, tok, cache,
                                                           lanes)
    finally:
        for k, v in was.items():
            if v is None:
                del os.environ[k]
            else:
                os.environ[k] = v
    return cfg, lowered.compile(), kernels.RESOLVED - before


def test_mla_decode_step_never_copies_the_latent_cache(deepseek_decode):
    """The latent rows are written in place into the donated stack and the
    latent kernel reads them where they lie: no instruction makes a buffer
    of one layer's latent cache (256 x 4096 x 576 bf16) or more — no copy,
    slice, convert or allocation — and the whole cache is aliased."""
    cfg, compiled, _ = deepseek_decode
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    a = cfg.mla
    layer = MOE_SLOTS * MAX_LEN * (a.kv_lora_rank + a.qk_rope_dim) * 2
    assert cache_sized_buffers(text, layer) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer, mem
    assert mem.alias_size_in_bytes >= cfg.n_layers * layer


def test_mla_decode_step_reads_in_place_and_runs_the_expert_kernel(
        deepseek_decode):
    """5 of 5 latent layers read in place, none sliced; the held experts'
    matmuls took the Pallas grouped matmul."""
    cfg, _, took = deepseek_decode
    assert took[("decode_cache", "in_place")] == cfg.n_layers == 5, took
    assert took[("decode_cache", "sliced")] == 0, took
    assert took[("flash_decode", "pallas")] >= 1, took
    assert took[("expert_gmm", "pallas")] >= 1, took
    assert not any(impl != "pallas" for (op, impl) in took
                   if op == "expert_gmm"), took
