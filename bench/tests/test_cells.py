"""CPU rehearsals of every kind of cell's run at toy sizes, the control that
must come out not correct, and the faults of the timed path that must make
``correct`` false. The look for a chip is steered to a stand-in TPU."""
from __future__ import annotations

import dataclasses

import pytest

from bench.tests import tiny

import harness  # noqa: E402
import limits_probe  # noqa: E402

SERVE, VIT = "tiny-lm.tchat", "tiny-vit.tclassify"


@pytest.fixture
def root(tmp_path, monkeypatch):
    # the persistent compile cache is process-wide: leave it alone here
    monkeypatch.setattr(harness, "use_compile_cache", lambda root: "off")
    return tiny.make_root(tmp_path)


@pytest.mark.parametrize("workload,trace", [(SERVE, 0), (SERVE, 1),
                                            (VIT, 0), (VIT, 1)])
def test_rehearsal(root, monkeypatch, workload, trace):
    out = tiny.run_cell(root, monkeypatch, workload, trace=trace)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compiles_in_window"] == 0
    names = set(out["metrics"])
    if trace:
        assert out["device"]["window_s"] > 0
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert names     # host-side per-layer metrics; device ones need a chip
    else:
        assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("workload", [SERVE, VIT])
def test_control_is_not_correct(root, monkeypatch, workload):
    def device_check(chips):
        import jax
        return dict(tiny.DEVICE, devices=jax.devices()[:chips])
    monkeypatch.setattr(harness, "device_check", device_check)
    got, name = limits_probe.readings(root, workload, [11, 12, 13], 2.0,
                                      allowed=("pallas", "ref", "xla"))
    limit = tiny.LIMITS[workload][name]
    print(f"{workload} {name}: limit {limit}, readings {got}")
    for _, prog, ctrl in got:
        assert prog < limit < ctrl


def _serve_fault(monkeypatch, kind):
    from repro.serve import ServeEngine
    argmax, decode = ServeEngine._argmax, ServeEngine._decode_impl

    if kind == "token_altered":
        monkeypatch.setattr(ServeEngine, "_argmax", lambda self, lg: (
            argmax(self, lg) + 1) % self.cfg.vocab_size)
    elif kind == "state_unchanged":
        monkeypatch.setattr(ServeEngine, "_decode_impl",
                            lambda self, p, tok, cache: (
                                decode(self, p, tok, cache)[0], cache))
    elif kind == "half_batch":
        def half(self, p, tok, cache):
            nxt, cache = decode(self, p, tok, cache)
            h = nxt.shape[0] // 2     # the lanes a light load fills first
            return nxt.at[:h].set(tok[:h, 0]), cache
        monkeypatch.setattr(ServeEngine, "_decode_impl", half)


def _vit_fault(monkeypatch, kind):
    import repro.models
    build = repro.models.build_model

    def broken(cfg):
        model = build(cfg)

        def apply(p, batch):
            y = model.apply(p, batch)
            if kind == "answer_altered":
                return y.at[0, 0].add(1.0)
            h = y.shape[0] // 2
            return y.at[h:].set(y[:h])
        return dataclasses.replace(model, apply=apply)
    monkeypatch.setattr(repro.models, "build_model", broken)


@pytest.mark.parametrize("workload,kind", [
    (SERVE, "token_altered"), (SERVE, "state_unchanged"),
    (SERVE, "half_batch"), (VIT, "answer_altered"), (VIT, "half_batch")])
def test_fault_is_not_correct(root, monkeypatch, workload, kind):
    (_serve_fault if workload == SERVE else _vit_fault)(monkeypatch, kind)
    out = tiny.run_cell(root, monkeypatch, workload)
    assert not out["correct"], out["checks"]
