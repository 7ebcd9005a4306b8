"""Training driver with checkpoint/restart, mesh sharding and logging.

Examples (CPU-sized):
    PYTHONPATH=src python -m repro.launch.train --arch deit-tiny-reduced \
        --steps 200 --batch 32 --ckpt /tmp/ckpt
    PYTHONPATH=src python -m repro.launch.train --arch granite-8b-reduced \
        --steps 50 --batch 8 --seq 64 --mesh 1x1

On a real cluster the same entry point runs with --mesh 16x16 (or 2x16x16)
and the full config names; everything below is mesh-size agnostic.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.configs import get_config, reduced
from repro.data import lm_batch, vit_batch
from repro.distrib import sharding as shard_mod
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import AdamWConfig, adamw_init, adamw_update, warmup_cosine


def resolve_config(name: str):
    if name.endswith("-reduced"):
        return reduced(get_config(name[: -len("-reduced")]))
    return get_config(name)


def make_train_step(model, ocfg, *, peak_lr, total_steps):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, batch))(params)
        lr = warmup_cosine(opt_state["step"], peak=peak_lr,
                           warmup=max(10, total_steps // 20),
                           total=total_steps)
        new_p, new_o, metrics = adamw_update(params, grads, opt_state, lr,
                                             ocfg)
        return new_p, new_o, loss, metrics["grad_norm"]
    return train_step


def data_for(cfg, step, *, batch, seq, seed=0):
    if cfg.family == "vit":
        return vit_batch(step, batch=batch, img=cfg.img_size,
                         n_classes=max(2, cfg.n_classes), seed=seed)
    b = lm_batch(step, batch=batch, seq=seq, vocab=cfg.vocab_size, seed=seed)
    if cfg.family == "encdec":
        rng = np.random.RandomState(seed * 77 + step)
        b = dict(b, frames=jnp.asarray(
            rng.randn(batch, seq, cfg.d_model).astype(np.float32)))
    if cfg.frontend == "patch_stub":
        rng = np.random.RandomState(seed * 79 + step)
        b = dict(b, patch_embeds=jnp.asarray(
            rng.randn(batch, 8, cfg.d_model).astype(np.float32)))
    return b


def train(cfg, *, steps, batch, seq, ckpt_dir=None, mesh_shape=None,
          peak_lr=3e-4, save_every=50, log_every=10, seed=0,
          fsdp=False, log=print):
    model = build_model(cfg)
    ocfg = AdamWConfig()
    mesh = make_mesh(mesh_shape) if mesh_shape else None
    params = model.init(jax.random.PRNGKey(seed)) if mesh is None else \
        shard_mod.init_sharded(model.init, jax.random.PRNGKey(seed), mesh,
                               fsdp=fsdp)
    opt_state = adamw_init(params, ocfg)
    step0 = 0

    ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
    if ckpt_dir:
        last = latest_step(ckpt_dir)
        if last is not None:
            (params, opt_state), extra = restore_checkpoint(
                ckpt_dir, last, (params, opt_state))
            step0 = last
            log(f"[train] resumed from step {last}")

    step_fn = make_train_step(model, ocfg, peak_lr=peak_lr,
                              total_steps=steps)
    if mesh is not None:
        pspecs = shard_mod.param_specs(params, mesh, fsdp=fsdp)
        pshard = shard_mod.shardings_of(pspecs, mesh)
        oshard = shard_mod.shardings_of(
            shard_mod.param_specs(opt_state, mesh, fsdp=fsdp), mesh)
        jit_step = jax.jit(step_fn, in_shardings=(pshard, oshard, None),
                           out_shardings=(pshard, oshard, None, None))
        params = jax.device_put(params, pshard)
        opt_state = jax.device_put(opt_state, oshard)
    else:
        jit_step = jax.jit(step_fn)

    losses = []
    t0 = time.time()
    ctx = mesh or _nullcontext()
    with ctx:
        for step in range(step0, steps):
            b = data_for(cfg, step, batch=batch, seq=seq, seed=seed)
            params, opt_state, loss, gn = jit_step(params, opt_state, b)
            losses.append(float(loss))
            if (step + 1) % log_every == 0:
                dt = (time.time() - t0) / log_every
                log(f"[train] step {step+1}/{steps} loss {float(loss):.4f} "
                    f"gnorm {float(gn):.3f} {dt*1e3:.0f} ms/step")
                t0 = time.time()
            if ckpt and ((step + 1) % save_every == 0 or step + 1 == steps):
                ckpt.save(step + 1, (params, opt_state))
    if ckpt:
        ckpt.wait()
    return params, opt_state, losses


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help="config id; append '-reduced' for the CPU-size variant")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x2 (axes data,model) or 2x4x4 (pod,data,model)")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = resolve_config(args.arch)
    mesh_shape = tuple(int(x) for x in args.mesh.split("x")) if args.mesh \
        else None
    _, _, losses = train(cfg, steps=args.steps, batch=args.batch,
                         seq=args.seq, ckpt_dir=args.ckpt,
                         mesh_shape=mesh_shape, peak_lr=args.lr,
                         save_every=args.save_every, fsdp=args.fsdp,
                         seed=args.seed)
    print(f"[train] final loss {losses[-1]:.4f} "
          f"(first {losses[0]:.4f})")


if __name__ == "__main__":
    main()
