#!/usr/bin/env python3
"""Bring-up smoke on a TPU: the system's two accelerator paths, driven once
through the entry points a user calls.

    python chip_smoke.py                # one chip: sweep, train, kernels
    python chip_smoke.py --four-chips   # four chips: the FSDP train phase
                                        # on a (4, 1) mesh vs one device

Phases (one chip):
  sweep    `run_sweep` over cluster/dp grown to 256 nodes x 8 devices, 64
           Monte-Carlo samples plus the healthy reference, as one compiled
           scan; then a 4-node sweep checked against per-sample ClusterSim.
  train    the `repro.launch.train` Trainer on qwen3-4b at its published
           widths, cut to 2 layers, with the Lit Silicon hook on: 5 steps
           of 4 x 512 tokens, then one checkpoint.
  kernels  flash attention and RMSNorm compiled for the chip at qwen3-4b
           widths, against their jnp oracles.

Every check that fails stops the script with a non-zero exit.  JAX must
find a TPU: there is no CPU fallback.  The last line of output is
``{"ok": true, "device": {...}}``.  Times printed are smoke timings (one
run, host clock, ended by ``block_until_ready``), not benchmark numbers.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
OUT = ROOT / ".chip_smoke"           # the script's own output (gitignored)

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import numpy as np                                  # noqa: E402
from jax.sharding import Mesh                       # noqa: E402

from repro.compile_cache import use_compile_cache   # noqa: E402

SWEEP_NODES, SWEEP_SAMPLES = 256, 64
TRAIN_LAYERS, TRAIN_STEPS = 2, 5
TRAIN_BATCH, TRAIN_SEQ = 4, 512
LOSS_RTOL = 1e-2           # four-chip vs one-device loss, every step
SWEEP_RTOL = 5e-3          # scan vs per-sample ClusterSim, tail means
KERNEL_TOL = 2e-2          # bf16 kernel vs oracle: |d| <= tol + tol * |ref|
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    print(phase + " " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def require_tpu():
    """The first device, which must be a TPU; exits non-zero otherwise."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found {dev.platform!r} devices "
                         f"and no TPU; this smoke runs on the chip only")
    return dev


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling while active."""

    def __init__(self):
        self.seconds = 0.0

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)


# --------------------------------------------------------------------------- #
# sweep
# --------------------------------------------------------------------------- #
def _register_wide_dp(nodes: int) -> str:
    """cluster/dp with its fleet grown to ``nodes`` nodes, registered."""
    from repro.api.registry import get_scenario, register
    from repro.api.spec import with_overrides

    name = f"cluster/dp-{nodes}"

    def wide_dp():
        sc = with_overrides(get_scenario("cluster/dp"),
                            {"fleet.n_nodes": nodes})
        return sc.replace(name=name, description=f"cluster/dp at {nodes} "
                          f"nodes x 8 devices")
    register(wide_dp)
    return name


def phase_sweep(nodes: int = SWEEP_NODES, samples: int = SWEEP_SAMPLES,
                iterations=None) -> None:
    from repro.api.sweep import SweepSpec, run_sweep

    spec = SweepSpec(scenario=_register_wide_dp(nodes), samples=samples,
                     seed=0, iterations=iterations)
    with CompileClock() as clock:
        t0 = time.perf_counter()
        art = run_sweep(spec)
        first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = run_sweep(spec)
    run_s = time.perf_counter() - t0
    say("sweep", scenario=spec.scenario, nodes=nodes, devices_per_node=8,
        samples=art["n_samples"], iterations=art["iterations"],
        engine=art["engine"])
    say("sweep", compile_s=f"{clock.seconds:.3f}")
    say("sweep", first_call_s=f"{first_s:.3f}")
    say("sweep", run_s=f"{run_s:.3f}")
    check(art["engine"] == "jax-scan",
          f"sweep ran on engine {art['engine']!r}, not the compiled scan")
    check(art["n_samples"] == samples, "sweep lost samples")
    values = [q for s in art["summary"].values() for q in s.values()]
    values += list(art["reference"].values())
    check(all(math.isfinite(v) for v in values),
          "sweep quantiles or reference not finite")
    check(warm["summary"] == art["summary"], "sweep not reproducible")
    rec = art["summary"]["recovery"]
    say("sweep", recovery_p10=rec["p10"], recovery_p50=rec["p50"],
        recovery_p90=rec["p90"],
        reference_t_fleet_s=art["reference"]["t_fleet_s"])


def phase_sweep_reference(samples: int = 3, iterations: int = 40) -> None:
    """The compiled scan against per-sample ClusterSim on the host: same
    thermal lotteries, another noise stream, so tail means agree within
    SWEEP_RTOL (the tolerance tests/test_jax_engine.py holds them to)."""
    from repro.api.registry import get_scenario
    from repro.api.spec import with_overrides
    from repro.api.sweep import (_HEALTHY, SweepSpec, _run_one_python,
                                 _sample_overrides, run_sweep)

    spec = SweepSpec(scenario="cluster/dp", samples=samples, seed=0,
                     iterations=iterations)
    art = run_sweep(spec)
    check(art["engine"] == "jax-scan", "reference sweep not on the scan")
    base = get_scenario("cluster/dp").replace(manager=None)
    rows = [_run_one_python(with_overrides(base, ov), seed, iterations)
            for _, ov, seed in _sample_overrides(spec, base)]
    rows.append(_run_one_python(with_overrides(base, dict(_HEALTHY)),
                                base.seed, iterations))
    worst = 0.0
    for scan, host in zip(art["samples"] + [art["reference"]], rows):
        for key in ("t_fleet_s", "throughput", "fleet_power_w"):
            worst = max(worst, abs(scan[key] / host[key] - 1.0))
    say("sweep_reference", samples=samples, iterations=iterations,
        max_rel_diff=f"{worst:.3e}", tol=SWEEP_RTOL)
    check(worst <= SWEEP_RTOL, "scan disagrees with per-sample ClusterSim")


# --------------------------------------------------------------------------- #
# train
# --------------------------------------------------------------------------- #
def qwen3_depth_cut(layers: int = TRAIN_LAYERS):
    from repro.configs import get_config
    full = get_config("qwen3-4b")
    cfg = full.replace(n_layers=layers)
    say("train", model=full.name, layers=f"{layers}/{full.n_layers}",
        cut="depth only", d_model=cfg.d_model,
        heads=f"{cfg.n_heads}q/{cfg.n_kv_heads}kv x {cfg.head_dim}",
        d_ff=cfg.d_ff, vocab=cfg.vocab_size, qk_norm=cfg.qk_norm,
        tied_embeddings=cfg.tie_embeddings,
        params_m=f"{cfg.param_count() / 1e6:.1f}")
    return cfg


def train(model_cfg, mesh: Mesh, tag: str, steps: int = TRAIN_STEPS,
          batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ) -> dict:
    """``steps`` steps of the launch.train Trainer on ``mesh``, then one
    checkpoint, written to and removed from this script's own directory."""
    from repro.launch.train import build_parser, build_trainer

    ckpt_dir = OUT / f"checkpoints-{tag}"
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    args = build_parser().parse_args([
        "--arch", "qwen3-4b", "--steps", str(steps),
        "--global-batch", str(batch), "--seq-len", str(seq),
        "--use-case", "gpu-red", "--checkpoint-dir", str(ckpt_dir),
        "--checkpoint-every", "0"])
    trainer = build_trainer(args, model_cfg=model_cfg, mesh=mesh)
    step_s = []
    for _ in range(steps):
        t0 = time.perf_counter()
        trainer.run(1)
        jax.block_until_ready(trainer.state)
        step_s.append(time.perf_counter() - t0)
    losses = [m["loss"] for m in trainer.metrics_log]
    t0 = time.perf_counter()
    trainer.save()
    trainer.ckpt.wait()
    ckpt_s = time.perf_counter() - t0
    saved = trainer.ckpt.latest_step()
    shutil.rmtree(ckpt_dir, ignore_errors=True)

    say(f"train[{tag}]", mesh=dict(mesh.shape), tokens_per_step=batch * seq,
        steps=len(losses))
    say(f"train[{tag}]", losses=[round(x, 6) for x in losses])
    say(f"train[{tag}]", first_step_s=f"{step_s[0]:.3f}")
    say(f"train[{tag}]", step_s_after_warmup=[f"{s:.4f}" for s in step_s[1:]])
    say(f"train[{tag}]", checkpoint_s=f"{ckpt_s:.3f}", checkpoint_step=saved)
    caps = trainer.hooks[0].backend.get_power_caps()
    say(f"train[{tag}]", lit_silicon_caps_w=np.round(caps, 0).tolist())
    check(len(losses) == steps, f"{tag}: {len(losses)} of {steps} steps")
    check(all(math.isfinite(x) for x in losses), f"{tag}: loss not finite")
    check(losses[-1] < losses[0], f"{tag}: loss did not fall")
    check(saved == steps, f"{tag}: checkpoint of step {steps} missing")
    return {"losses": losses, "trainer": trainer}


def peak_bytes(devices) -> list:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def phase_train() -> None:
    dev = jax.devices()[0]
    mesh = Mesh(np.array([dev]).reshape(1, 1), ("data", "model"))
    train(qwen3_depth_cut(), mesh, "1chip")
    say("train[1chip]", peak_bytes_in_use=peak_bytes([dev]))


def phase_four_chips() -> None:
    """The train phase on a (data, model) = (4, 1) mesh, then the same
    steps on one device, in this process; losses must agree."""
    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    cfg = qwen3_depth_cut()
    mesh4 = Mesh(np.array(devs).reshape(4, 1), ("data", "model"))
    r4 = train(cfg, mesh4, "4chip")
    params = jax.tree_util.tree_leaves(r4["trainer"].state.params)
    spans = sorted({len(p.sharding.device_set) for p in params})
    split = sum(1 for p in params if "data" in str(p.sharding.spec))
    say("train[4chip]", param_leaves=len(params), devices_spanned=spans,
        leaves_split_over_data=split)
    check(spans == [4], "a parameter does not span the four devices")
    say("train[4chip]", peak_bytes_in_use=peak_bytes(devs))
    losses4 = r4["losses"]
    del r4, params
    gc.collect()

    mesh1 = Mesh(np.array(devs[:1]).reshape(1, 1), ("data", "model"))
    losses1 = train(cfg, mesh1, "1chip")["losses"]
    worst = max(abs(a / b - 1.0) for a, b in zip(losses4, losses1))
    say("train[4chip_vs_1chip]", max_rel_loss_diff=f"{worst:.3e}",
        tol=LOSS_RTOL)
    check(worst <= LOSS_RTOL, "four-chip losses differ from one device")


# --------------------------------------------------------------------------- #
# kernels
# --------------------------------------------------------------------------- #
def _close(name: str, fn, args, ref) -> None:
    """Run a kernel compiled for the chip and check it against its oracle,
    and that the program holds the Mosaic kernel (not interpret mode)."""
    compiled = jax.jit(fn).lower(*args).compile()
    mosaic = "tpu_custom_call" in compiled.as_text()
    out = jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(*args))
    call_s = time.perf_counter() - t0
    o = np.asarray(out, np.float32)
    r = np.asarray(ref, np.float32)
    diff = np.abs(o - r)
    say(f"kernel[{name}]", shape=list(o.shape),
        max_abs_diff=f"{np.max(diff):.3e}", tol=KERNEL_TOL, mosaic=mosaic,
        warm_call_s=f"{call_s:.5f}")
    check(mosaic, f"{name}: no Mosaic kernel in the compiled program")
    check(bool(np.all(np.isfinite(o))), f"{name}: output not finite")
    check(bool(np.all(diff <= KERNEL_TOL * (1.0 + np.abs(r)))),
          f"{name}: disagrees with its oracle")


def phase_kernels(seq: int = 4096) -> None:
    from repro.configs import get_config
    from repro.kernels import interpret_mode
    from repro.kernels.flash_attention import (flash_attention,
                                               flash_attention_ref)
    from repro.kernels.rmsnorm import rmsnorm, rmsnorm_ref

    check(not interpret_mode(), "Pallas would run interpreted")
    cfg = get_config("qwen3-4b")
    H, kvH, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kq, kk, kv, kx, kw = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(kq, (1, seq, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (1, seq, kvH, D), jnp.bfloat16)
    v = jax.random.normal(kv, (1, seq, kvH, D), jnp.bfloat16)

    def fa(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def flat(x):                       # (1, S, h, D) -> (H, S, D), GQA
        x = jnp.repeat(x, H // x.shape[2], axis=2)
        return x.transpose(0, 2, 1, 3).reshape(H, seq, D)

    ref = flash_attention_ref(flat(q), flat(k), flat(v), causal=True)
    ref = ref.reshape(1, H, seq, D).transpose(0, 2, 1, 3)
    _close("flash_attention", fa, (q, k, v), ref)

    x = jax.random.normal(kx, (8192, cfg.d_model), jnp.bfloat16)
    w = 1.0 + 0.1 * jax.random.normal(kw, (cfg.d_model,), jnp.float32)
    _close("rmsnorm", rmsnorm, (x, w), rmsnorm_ref(x, w))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the FSDP train phase on four chips, "
                         "compared with one device")
    args = ap.parse_args(argv)

    cache = use_compile_cache()
    dev = require_tpu()
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(jax.devices()), compile_cache=cache)
    if args.four_chips:
        phase_four_chips()
    else:
        phase_sweep()
        phase_sweep_reference()
        phase_train()
        phase_kernels()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
