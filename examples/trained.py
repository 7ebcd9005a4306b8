"""Small trained models shared by the examples (cached under examples/_cache).

CORP needs *trained* networks (random weights have nearly isotropic
activations — App. A's redundancy only exists after training), so the
examples reuse a DeiT-family ViT and a markov-LM trained for a few hundred
CPU steps, cached after the first run.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import get_config, reduced
from repro.data import lm_batch, vit_batch
from repro.launch.train import train
from repro.models import build_model

CACHE = os.path.join(os.path.dirname(__file__), "_cache")

VIT_TASK = {"noise": 2.0, "iid_noise": 0.5, "n_classes": 32}


def vit_cfg():
    return reduced(get_config("deit-base")).replace(
        name="deit-example", n_layers=4, d_model=96, n_heads=4,
        n_kv_heads=4, d_head=24, d_ff=384, img_size=32, patch=8,
        n_classes=VIT_TASK["n_classes"])


def vit_task_batch(step: int, batch: int, img: int):
    """The example vision task (difficulty tuned so 50-70% naive pruning
    visibly hurts while the dense model sits near ~80%)."""
    return vit_batch(step, batch=batch, img=img,
                     n_classes=VIT_TASK["n_classes"], seed=0,
                     noise=VIT_TASK["noise"], iid_noise=VIT_TASK["iid_noise"])


def lm_cfg():
    return reduced(get_config("qwen2-1.5b")).replace(
        name="lm-example", n_layers=4, d_model=96, n_heads=4, n_kv_heads=2,
        d_head=24, d_ff=384, vocab_size=256, dtype="float32")


def _cached_train(tag, cfg, steps, batch, seq=48, data_fn=None):
    ckpt_dir = os.path.join(CACHE, tag)
    model = build_model(cfg)
    last = latest_step(ckpt_dir)
    if last is not None and last >= steps:
        params = model.init(jax.random.PRNGKey(0))
        (params, _), _ = restore_checkpoint(ckpt_dir, last, (params, None))
        return model, params
    if data_fn is None:
        params, _opt, _losses = train(cfg, steps=steps, batch=batch, seq=seq,
                                      ckpt_dir=None, peak_lr=2e-3,
                                      log=lambda *a: None)
    else:
        from repro.optim import AdamWConfig, adamw_init, adamw_update
        ocfg = AdamWConfig()
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw_init(params, ocfg)

        @jax.jit
        def step_fn(p, o, b):
            loss, g = jax.value_and_grad(lambda pp: model.loss(pp, b))(p)
            return (*adamw_update(p, g, o, 2e-3, ocfg)[:2], loss)

        for s in range(steps):
            params, opt, _ = step_fn(params, opt, data_fn(s, batch))
    save_checkpoint(ckpt_dir, steps, (params, None))
    return model, params


def trained_vit(steps: int = 300):
    cfg = vit_cfg()
    return cfg, *_cached_train(
        "vit", cfg, steps, batch=64,
        data_fn=lambda s, b: vit_task_batch(s, b, cfg.img_size))


def trained_lm(steps: int = 300):
    cfg = lm_cfg()
    return cfg, *_cached_train("lm", cfg, steps, batch=16, seq=48)


# ---------------------------------------------------------------------------
# evaluation
# ---------------------------------------------------------------------------

def vit_eval_acc(model, params, *, n=512, seed=9_000):
    cfg = model.cfg
    correct = total = 0
    f = jax.jit(lambda p, x: model.apply(p, {"images": x}))
    for i in range(n // 64):
        b = vit_task_batch(seed + i, 64, cfg.img_size)
        logits = f(params, b["images"])
        correct += int(jnp.sum(jnp.argmax(logits, -1) == b["labels"]))
        total += 64
    return correct / total


def lm_eval_ppl(model, params, *, n=8, seed=9_500):
    cfg = model.cfg
    tot, cnt = 0.0, 0

    @jax.jit
    def nll(p, b):
        return model.loss(p, b, train=False)
    for i in range(n):
        b = lm_batch(seed + i, batch=8, seq=48, vocab=cfg.vocab_size, seed=0)
        tot += float(nll(params, b)) * 8 * 48
        cnt += 8 * 48
    return float(np.exp(tot / cnt))


def calib_vit(cfg, n_samples=128, batch=16):
    steps = max(1, n_samples // batch)

    def make():
        for i in range(steps):
            b = vit_task_batch(20_000 + i, batch, cfg.img_size)
            yield {"images": b["images"]}
    return make


def calib_lm(cfg, n_samples=64, batch=8, seq=48):
    steps = max(1, n_samples // batch)

    def make():
        for i in range(steps):
            b = lm_batch(30_000 + i, batch=batch, seq=seq,
                         vocab=cfg.vocab_size, seed=0)
            yield {"tokens": b["tokens"]}
    return make


def forward_flops(model, batch):
    from repro.roofline.analysis import jaxpr_matmul_flops
    return jaxpr_matmul_flops(lambda p, b: model.apply(p, b),
                              jax.eval_shape(lambda: model.init(
                                  jax.random.PRNGKey(0))), batch)


def params_of(params) -> int:
    return int(sum(x.size for x in jax.tree.leaves(params)))
