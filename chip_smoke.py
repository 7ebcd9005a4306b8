#!/usr/bin/env python3
"""Run CORP pruning and pruned serving end to end on a TPU, and check them.

    python chip_smoke.py            # one chip: the prune and serve phases
    python chip_smoke.py --chips 4  # four chips: only the sharded paths

One process, in phases, through the entry points a user calls:

* ``prune``: ``repro.launch.prune`` on ``deit-base`` at full width (fp32)
  at 50% MLP and attention sparsity, with and without CORP's closed-form
  compensation. The compensated model must be closer to the dense one on
  held-out images than the rank-only one (the paper's claim), and the
  Pallas gram must match a float64 X^T X on a real-width MLP tap.
* ``serve``: ``repro.launch.serve.serve_trace`` (front-end -> scheduler ->
  engine -> slot cache) on ``qwen2-1.5b`` at full width (bf16), dense and
  at ``--sparsity 0.5``. Every request must end DONE, and one request's
  per-step engine logits must match the model's full forward, teacher-
  forced on the engine's tokens.
* ``--chips 4``: sharded calibration of ``deit-base`` on a (1, 4) mesh
  against the same run on one chip; ``deepseek-7b`` served at full size on
  (1, 4); and ``deepseek-7b`` cut to 6 layers served on (1, 4) against the
  full forward on one chip.

Every main-path op must resolve to its Pallas kernel. Any failed check
exits non-zero before the result line. The last line of a passing run is
``{"ok": true, "device": {...}}``. Without a TPU the script stops before any
phase. Wall times are set-up including compilation, never speeds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

# relative L2 error of engine logits against the full forward, per step.
# Both sides run bf16 weights and activations, and reduce in different
# orders (cached decode against one causal pass), so they agree to bf16's
# few significant digits, compounded over the layers.
LOGIT_TOL = 5e-2
GRAM_TOL = 1e-2          # docs/kernels.md: max|ds2| / max|s2|
# sharded calibration against one chip. On the TPU both layouts multiply
# fp32 in one bf16 pass (default precision), and the ridge solves amplify
# the rounding of the statistics into the null space of the fold, so the
# two pruned models differ: they must stay far closer to each other than
# either is to the dense model (5% of the CORP error), and the statistics
# themselves within the gram kernel's bf16 tolerance.
STATS_TOL = GRAM_TOL
SHARD_TOL = 5e-2
OVERRIDES = ("REPRO_GRAM_IMPL", "REPRO_ATTN_IMPL", "REPRO_DECODE_IMPL",
             "REPRO_WKV_IMPL", "REPRO_GRAM_TILES")
ON_PATH = {"prune": ("gram", "flash_attention"),
           "serve": ("flash_attention", "flash_decode"),
           "calib-sharded": ("gram", "flash_attention"),
           "serve-sharded": ("flash_attention", "flash_decode")}


def fail(msg):
    raise SystemExit(f"chip_smoke: {msg}")


class Checks:
    """Collects failed checks so that one chip run reports every phase."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok, what):
        print(f"[check] {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def rel(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# prune
# ---------------------------------------------------------------------------

def vit_logits(model, params, images):
    import jax
    return jax.jit(lambda p, x: model.apply(p, {"images": x}))(params, images)


def heldout_images(cfg, n):
    from repro.data import vit_batch
    return vit_batch(20_000, batch=n, img=cfg.img_size,
                     n_classes=max(cfg.n_classes, 2), seed=4321)["images"]


def prune_phase(check, arch="deit-base", calib=64, batch=8, heldout=16):
    import jax
    import numpy as np
    from repro.kernels.gram import ops as gram_ops
    from repro.launch import prune as prune_cli
    from repro.models import build_model

    argv = ["--arch", arch, "--sparsity", "0.5", "--calib", str(calib),
            "--calib-batch", str(batch)]
    model, dense, pruned, pcfg, _ = prune_cli.main(argv)
    _, _, plain, _, _ = prune_cli.main(argv + ["--no-compensate"])
    cfg = model.cfg
    check(pcfg.eff_d_ff == cfg.d_ff // 2,
          f"d_ff {cfg.d_ff} -> {pcfg.eff_d_ff}")
    check(pcfg.eff_qk == cfg.qk_full // 2,
          f"qk per head {cfg.qk_full} -> {pcfg.eff_qk}")

    x = heldout_images(cfg, heldout)
    pmodel = build_model(pcfg)
    y = vit_logits(model, dense, x)
    e_corp = rel(vit_logits(pmodel, pruned, x), y)
    e_plain = rel(vit_logits(pmodel, plain, x), y)
    print(f"[prune] held-out relative logit error: CORP {e_corp!r}, "
          f"uncompensated {e_plain!r}")
    check(e_corp < e_plain, "CORP error < uncompensated error")

    # one real-width MLP tap: layer 0's hidden activations, (B*T, d_ff)
    def tap_h(p, imgs):
        taps = {}
        model.apply(p, {"images": imgs}, taps=taps)
        return taps["seg0/p0/h"][0]
    h = jax.jit(tap_h)(dense, x[:batch])
    h = h.reshape(-1, h.shape[-1])
    s2 = np.asarray(jax.jit(lambda a: gram_ops.gram(a)["s2"])(h))
    h64 = np.asarray(h, np.float64)
    ref = h64.T @ h64
    err = float(np.max(np.abs(s2 - ref)) / np.max(np.abs(ref)))
    print(f"[prune] gram {tuple(h.shape)} fp32 vs float64 X^T X: "
          f"max|ds2|/max|s2| = {err!r} (tolerance {GRAM_TOL})")
    check(err <= GRAM_TOL, f"gram max-relative error <= {GRAM_TOL}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def probe_engine_cls():
    """``ServeEngine`` whose jitted steps also hand every lane's last-position
    logits to the host, filed under the request that lane serves."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import ServeEngine

    class LogitProbe(ServeEngine):
        def __init__(self, *a, **kw):
            self.steps, self._seen = {}, []
            super().__init__(*a, **kw)

        def _argmax(self, logits):
            jax.debug.callback(
                lambda lg: self._seen.append(np.asarray(lg)),
                logits[:, -1, :self.cfg.vocab_size].astype(jnp.float32))
            return super()._argmax(logits)

        def _file(self, lanes):
            jax.effects_barrier()
            seen, self._seen = self._seen, []
            for lg in seen:
                for i, rid in enumerate(lanes):
                    if rid is not None:
                        self.steps.setdefault(rid, []).append(lg[i])

        def continue_admit(self, slot, budget=None):
            done = super().continue_admit(slot, budget)
            self._file([self.slots[slot].rid])
            return done

        def decode_step(self):
            lanes = [s.rid if not s.free and s.pending is None else None
                     for s in self.slots]
            retired = super().decode_step()
            self._file(lanes)
            return retired

    return LogitProbe


def forward_parity(engine, model, ref_params, reqs, tag):
    """Serve ``reqs`` on a probe ``engine``; compare each step's logits with
    ``model``'s full forward on ``ref_params``, teacher-forced on the
    engine's tokens. Returns the worst per-step relative L2 error and the
    engine's completions."""
    import jax
    import numpy as np
    V = model.cfg.vocab_size
    fwd = jax.jit(lambda p, t: model.apply(p, {"tokens": t})[0])
    worst, agree, total = 0.0, 0, 0
    comps = engine.run(reqs)
    for comp in comps:
        req = next(r for r in reqs if r.rid == comp.rid)
        P, G = len(req.tokens), len(comp.tokens)
        seq = np.concatenate([req.tokens, comp.tokens[:-1]])[None]
        ref = np.asarray(fwd(ref_params, seq)[0, P - 1:, :V], np.float32)
        eng = np.stack(engine.steps[comp.rid])
        if eng.shape != ref.shape:
            raise AssertionError(f"{tag}: {eng.shape} engine logits for "
                                 f"{ref.shape} reference")
        worst = max(worst, max(rel(eng[j], ref[j]) for j in range(G)))
        agree += int(np.sum(np.argmax(ref, -1) == comp.tokens))
        total += G
    print(f"[{tag}] engine vs full forward: worst per-step relative L2 "
          f"{worst!r} (tolerance {LOGIT_TOL}); forward argmax = engine "
          f"token on {agree}/{total} steps")
    return worst, comps


def serve_phase(check, arch="qwen2-1.5b", n=8, slots=4, max_len=1024,
                prompt=(64, 512), gen=(32, 64)):
    import jax
    from repro.launch.serve import serve_trace
    from repro.launch.train import resolve_config
    from repro.models import build_model
    from repro.serve.queue import Status

    Probe = probe_engine_cls()
    for sp in (0.0, 0.5):
        tag = f"serve {arch} sparsity {sp}"
        cfg = resolve_config(arch)
        if sp:
            cfg = cfg.pruned(sp, sp)
        model = build_model(cfg)
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        n_params = sum(x.size for x in jax.tree.leaves(params))
        print(f"[{tag}] {cfg.n_layers} layers, d_model {cfg.d_model}, "
              f"d_ff {cfg.eff_d_ff}, qk {cfg.eff_qk}, {n_params} params "
              f"({cfg.dtype})", flush=True)
        handles, _ = serve_trace(model, params, n=n, slots=slots,
                                 max_len=max_len, prompt_range=prompt,
                                 gen_range=gen, seed=0)
        status = [h.status for h in handles]
        check(all(s == Status.DONE for s in status),
              f"{tag}: {len(status)} requests all DONE "
              f"({[s.value for s in status]})")
        req = handles[0].req
        probe = Probe(model, params, n_slots=slots, max_len=max_len)
        err, (comp,) = forward_parity(probe, model, params, [req], tag)
        check(err <= LOGIT_TOL, f"{tag}: logits parity <= {LOGIT_TOL}")
        print(f"[{tag}] rid {req.rid} alone equals its stream in the "
              f"served batch: {list(comp.tokens) == list(handles[0].tokens)}")
        del params, probe


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def calib_sharded_phase(check, arch="deit-base", calib=64, batch=8):
    import jax
    import numpy as np
    from repro.core.calibrate import run_pass
    from repro.core.units import discover_units
    from repro.data import calib_stream
    from repro.launch import prune as prune_cli
    from repro.launch.mesh import make_mesh
    from repro.models import build_model

    argv = ["--arch", arch, "--sparsity", "0.5", "--calib", str(calib),
            "--calib-batch", str(batch)]
    model, dense, p1, pcfg, _ = prune_cli.main(argv)
    _, _, p4, _, _ = prune_cli.main(argv + ["--mesh", "1x4",
                                            "--calib-sharded"])
    cfg = model.cfg
    units = discover_units(cfg)
    stream = calib_stream(cfg, n_samples=calib, batch=batch)
    one = run_pass(model, units, dense, stream())
    mesh = make_mesh((1, 4))
    with mesh:
        four = run_pass(model, units, dense, stream(), mesh=mesh)
    st = max(float(np.max(np.abs(np.asarray(a, np.float64) - b))
                   / max(np.max(np.abs(np.asarray(b, np.float64))), 1e-30))
             for a, b in zip(jax.tree.leaves(four), jax.tree.leaves(one)))
    print(f"[calib-sharded] pass-1 statistics, (1,4) mesh vs one chip: "
          f"max|d|/max|s| = {st!r} (tolerance {STATS_TOL})")
    check(st <= STATS_TOL, "sharded calibration statistics parity")

    x = heldout_images(cfg, 16)
    pmodel = build_model(pcfg)
    y = vit_logits(model, dense, x)
    y1, y4 = vit_logits(pmodel, p1, x), vit_logits(pmodel, p4, x)
    e1, e4, d = rel(y1, y), rel(y4, y), rel(y4, y1)
    print(f"[calib-sharded] held-out CORP error: one chip {e1!r}, (1,4) "
          f"mesh {e4!r}; between the two pruned models {d!r} (tolerance "
          f"{SHARD_TOL} x {e1!r})")
    check(d <= SHARD_TOL * e1, "sharded-pruned output parity")


def serve_sharded_phase(check, arch="deepseek-7b", layers=None, n=4,
                        slots=4, max_len=1024, prompt=(64, 256),
                        gen=(16, 32)):
    import jax
    from repro.distrib.sharding import init_sharded
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import serve_trace
    from repro.launch.train import resolve_config
    from repro.models import build_model
    from repro.serve import ServeSharding
    from repro.serve.engine import synthetic_trace
    from repro.serve.queue import Status

    cfg = resolve_config(arch)
    if layers:
        cfg = cfg.replace(n_layers=layers)
    tag = f"serve-sharded {arch} {cfg.n_layers} layers"
    model = build_model(cfg)
    sharding = ServeSharding(make_mesh((1, 4)))
    params = init_sharded(model.init, jax.random.PRNGKey(0), sharding.mesh)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[{tag}] {n_params} params ({cfg.dtype}) born sharded over "
          f"{dict(sharding.sizes)}", flush=True)
    if not layers:
        handles, _ = serve_trace(model, params, n=n, slots=slots,
                                 max_len=max_len, prompt_range=prompt,
                                 gen_range=gen, seed=0, sharding=sharding)
        status = [h.status for h in handles]
        check(all(s == Status.DONE for s in status),
              f"{tag}: {len(status)} requests all DONE "
              f"({[s.value for s in status]})")
        return
    reqs = synthetic_trace(n, cfg.vocab_size, seed=0, prompt_range=prompt,
                           gen_range=gen)
    probe = probe_engine_cls()(model, params, n_slots=slots,
                               max_len=max_len, sharding=sharding)
    chip0 = jax.device_put(params, jax.devices()[0])
    err, _ = forward_parity(probe, model, chip0, reqs, tag)
    check(err <= LOGIT_TOL, f"{tag}: (1,4) engine vs chip-0 forward "
                            f"logits parity <= {LOGIT_TOL}")


# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: prune + serve phases; 4: the sharded paths")
    args = ap.parse_args()

    set_env = [v for v in OVERRIDES if os.environ.get(v)]
    if set_env:
        fail(f"implementation overrides set: {set_env}")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: jax found {devs[0].platform} devices")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, found "
             f"{len(devs)}")
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro import kernels
    from repro.launch.compile_cache import use_compile_cache
    print(f"[smoke] {len(devs)} x {devs[0].device_kind} "
          f"({devs[0].platform}), jax {jax.__version__}, compile cache "
          f"{use_compile_cache()}", flush=True)

    phases = ([("calib-sharded", calib_sharded_phase),
               ("serve-sharded", serve_sharded_phase),
               ("serve-sharded", lambda c: serve_sharded_phase(c, layers=6))]
              if args.chips == 4 else
              [("prune", prune_phase), ("serve", serve_phase)])
    check = Checks()
    for name, phase in phases:
        kernels.RESOLVED.clear()
        t0 = time.perf_counter()
        try:
            phase(check)
        except Exception:                      # noqa: BLE001 — report all
            traceback.print_exc()
            check(False, f"phase {name} raised")
        wall = time.perf_counter() - t0
        took = dict(sorted((f"{op}:{impl}", n)
                           for (op, impl), n in kernels.RESOLVED.items()))
        print(f"[{name}] resolved implementations (traces): {took}")
        off = [k for k in took if not k.endswith(":pallas")
               and k != "decode_cache:in_place"]
        missing = [op for op in ON_PATH[name]
                   if f"{op}:pallas" not in took]
        check(not off and not missing,
              f"{name}: every op on the path ran its Pallas kernel"
              + (f" (off: {off}, missing: {missing})" if off or missing
                 else ""))
        print(f"[{name}] set-up wall time including compile {wall!r} s; "
              f"peak_bytes_in_use {[peak_bytes(d) for d in devs[:args.chips]]}",
              flush=True)

    stray = [m for m in ("repro.launch.dryrun", "repro.roofline.inspect")
             if m in sys.modules]
    check(not stray, f"no forced-host-device module imported {stray}")
    if check.failed:
        print(f"[smoke] {len(check.failed)} failed: {check.failed}")
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
