#!/usr/bin/env python3
"""Smoke run of the paper's TNN LM on a TPU, through the entry points a
user calls, at the published widths of ``tnn-lm-wt103`` (6 layers,
d_model 512, d_ff 1024, vocab 50265, float32) with random weights made
from a seed.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: data-parallel check only

One chip, phases in order; any failure exits non-zero with no result line:

* device  — JAX must find a TPU, and the kernel dispatch must resolve to
  compiled Pallas (``use_pallas=True interpret=False``).
* parity  — one forward of ``ski-tnn-lm-wt103`` and ``fd-tnn-lm-wt103``
  (b=8, n=512) with the Pallas kernels and with the jnp reference, both
  at highest matmul precision; max |Δlogits| is held to LOGIT_TOL.
* train   — 5 steps each of the tno / ski / fd models at seq 512 and
  global batch 8 through ``repro.launch.train`` (StepBuilder + Trainer):
  every loss finite, and the ski / fd kernel backwards ran with no
  reference fallback.
* serve   — ``fd-tnn-lm-wt103`` through Engine + Scheduler: 8 slots, 16
  greedy requests with seeded ragged prompts of 64-512 tokens, 64 new
  tokens each, max_len 1024; every outcome ``ok`` with all its tokens.

``--chips 4`` trains ``fd-tnn-lm-wt103`` for 3 steps on a (data=4,
model=1) mesh over all four chips and on one chip with the same global
batch, and holds the losses to LOSS_RTOL.

The last line of standard output is the result, a JSON object
``{"ok": true, "device": {...}}``; setup (compile) and steady seconds of
each phase are printed before it for the reader, not as a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SEED = 0
BATCH, SEQ = 8, 512
TRAIN_STEPS = 5
ARCHS = ("tnn-lm-wt103", "ski-tnn-lm-wt103", "fd-tnn-lm-wt103")
KERNEL_ARCHS = ("ski-tnn-lm-wt103", "fd-tnn-lm-wt103")
#: kernels vs reference: max|Δlogits| <= LOGIT_TOL * max(1, max|logits|)
LOGIT_TOL = 1e-3
SERVE = dict(arch="fd-tnn-lm-wt103", slots=8, requests=16, prompt_min=64,
             prompt_max=512, new_tokens=64, max_len=1024)
#: four chips vs one: |Δloss| <= LOSS_RTOL * |loss| at each of 3 steps,
#: with a learning rate at which 3 steps move the loss visibly
DP_STEPS = 3
DP_OPT = ("--lr", "1e-3", "--warmup", "1")
LOSS_RTOL = 5e-4


class SmokeError(RuntimeError):
    """A phase failed; the message says why."""


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ------------------------------------------------------------------ phases
def phase_device() -> dict:
    import jax
    from repro.kernels import backend
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SmokeError(f"no TPU: JAX found platform {dev.platform!r} "
                         f"({dev.device_kind}); this smoke run needs one")
    _say(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")
    _say(f"dispatch: {backend.describe()}")
    if not (backend.use_pallas_default() and not backend.resolve_interpret()):
        raise SmokeError("kernel dispatch is not compiled Pallas "
                         "(want use_pallas=True interpret=False)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def _params(cfg, seed):
    import jax
    from repro.models.transformer import init_model
    from repro.nn.params import unbox
    return unbox(init_model(jax.random.PRNGKey(seed), cfg))[0]


def phase_parity(cfg, batch: int, seq: int) -> float:
    """Kernel forward vs jnp-reference forward of one model, same
    params and tokens, both at highest matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.steps import StepBuilder
    params = _params(cfg, SEED)
    rng = np.random.default_rng(SEED)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)),
                         jnp.int32)
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, use_pallas in (("kernels", True), ("reference", False)):
            fwd = jax.jit(StepBuilder(cfg, use_pallas=use_pallas)
                          .make_forward())
            t0 = time.perf_counter()
            compiled = fwd.lower(params, {"tokens": tokens}).compile()
            setup = time.perf_counter() - t0
            has_kernel = "tpu_custom_call" in compiled.as_text()
            if has_kernel != use_pallas and jax.default_backend() == "tpu":
                raise SmokeError(f"{cfg.name} {name} forward: "
                                 f"tpu_custom_call present={has_kernel}")
            t0 = time.perf_counter()
            logits = compiled(params, {"tokens": tokens})
            logits.block_until_ready()
            _say(f"parity {cfg.name} {name}: setup {setup:.2f}s, "
                 f"forward {time.perf_counter() - t0:.3f}s")
            out[name] = logits
    ref = out["reference"]
    diff = float(jnp.max(jnp.abs(out["kernels"] - ref)))
    scale = max(1.0, float(jnp.max(jnp.abs(ref))))
    finite = bool(jnp.all(jnp.isfinite(out["kernels"])))
    _say(f"parity {cfg.name}: max|dlogits| {diff:.3e} "
         f"(limit {LOGIT_TOL * scale:.3e} = {LOGIT_TOL} x {scale:.3f}), "
         f"shape {tuple(ref.shape)}, finite={finite}")
    if not finite or not diff <= LOGIT_TOL * scale:
        raise SmokeError(f"{cfg.name}: kernels disagree with the reference "
                         f"(max|dlogits| {diff:.3e})")
    return diff


def phase_train(arch: str, steps: int, seq: int, batch: int, *,
                mesh=None, extra=()) -> list:
    """``steps`` training steps through repro.launch.train; returns the
    losses. ski / fd runs must show kernel forwards and backwards with no
    reference backward."""
    import numpy as np
    from repro.kernels import fd_fused, ski_vjp
    from repro.launch import train
    ski_vjp.reset_counters()
    fd_fused.reset_counters()
    args = train.parse_args(["--arch", arch, "--steps", str(steps),
                             "--seq-len", str(seq),
                             "--global-batch", str(batch),
                             "--seed", str(SEED), *extra])
    trainer, watch, _ = train.run(args, mesh=mesh)
    losses = [float(m["loss"]) for m in trainer.metrics_history]
    secs = trainer.step_seconds
    steady = (sum(secs[1:]) / len(secs[1:])) if len(secs) > 1 else float("nan")
    n_dev = mesh.devices.size if mesh is not None else "all"
    _say(f"train {arch} ({n_dev} device(s)): losses {losses}; setup "
         f"{secs[0]:.2f}s, steady {steady:.3f}s/step, "
         f"compiles {watch.count('train_step')}")
    if len(losses) != steps or not np.all(np.isfinite(losses)):
        raise SmokeError(f"train {arch}: losses {losses}")
    counters = {"ski": ski_vjp.counters, "fd": fd_fused.counters}
    for mixer, c in counters.items():
        if arch.startswith(f"{mixer}-"):
            _say(f"train {arch}: {mixer} kernel counters {dict(c)}")
            if c["fwd"] < 1 or c["bwd_kernel"] < 1 or c["bwd_ref"] != 0:
                raise SmokeError(f"train {arch}: kernel path not taken "
                                 f"({dict(c)})")
    return losses


def phase_serve(cfg, *, slots, requests, prompt_min, prompt_max,
                new_tokens, max_len) -> int:
    """Continuous-batching engine over seeded ragged greedy requests."""
    import numpy as np
    from repro.serving_engine import Engine, Request, Scheduler
    params = _params(cfg, SEED)
    t0 = time.perf_counter()
    eng = Engine(cfg, params, slots=slots, max_len=max_len)
    sched = Scheduler(eng)
    setup = time.perf_counter() - t0
    rng = np.random.default_rng(SEED)
    plens = rng.integers(prompt_min, prompt_max + 1, requests)
    for i, p in enumerate(plens):
        sched.submit(Request(uid=f"req{i}", max_new=new_tokens, seed=i,
                             prompt=rng.integers(0, cfg.vocab, int(p))
                             .astype(np.int32)))
    t0 = time.perf_counter()
    results, _ = sched.run()
    run_s = time.perf_counter() - t0
    by_status = {}
    for o in sched.outcomes.values():
        by_status[o.status] = by_status.get(o.status, 0) + 1
    short = [u for u in sched.outcomes if len(results.get(u, [])) != new_tokens]
    n_ok = by_status.get("ok", 0)
    _say(f"serve {cfg.name}: {n_ok}/{requests} ok, outcomes {by_status}, "
         f"prompt lens {sorted(int(p) for p in plens)}; engine setup "
         f"{setup:.2f}s, run {run_s:.2f}s (compiles included), "
         f"steps={sched.steps} prefills={sched.prefills} "
         f"(packed={sched.packed_prefills})")
    if n_ok != requests or short:
        raise SmokeError(f"serve: outcomes {by_status}, short {short}")
    return n_ok


# -------------------------------------------------------------- one / four
def run_one_chip() -> dict:
    from repro.configs import get_config
    device = phase_device()
    for arch in KERNEL_ARCHS:
        phase_parity(get_config(arch), BATCH, SEQ)
    for arch in ARCHS:
        phase_train(arch, TRAIN_STEPS, SEQ, BATCH)
    serve = dict(SERVE)
    phase_serve(get_config(serve.pop("arch")), **serve)
    return device


def phase_data_parallel(arch: str, steps: int, seq: int, batch: int,
                        extra=()) -> list:
    """Same seed, data and global batch on every device (data-parallel)
    and on the first device alone; the losses must agree."""
    import jax
    from repro.launch.mesh import make_host_mesh
    devs = jax.devices()
    dp = phase_train(arch, steps, seq, batch, mesh=make_host_mesh(devs),
                     extra=extra)
    one = phase_train(arch, steps, seq, batch,
                      mesh=make_host_mesh(devs[:1]), extra=extra)
    rel = [abs(a - b) / max(abs(b), 1e-12) for a, b in zip(dp, one)]
    _say(f"data-parallel {arch}: {len(devs)} devices {dp} vs one {one}; "
         f"max rel diff {max(rel):.3e} (limit {LOSS_RTOL})")
    if not max(rel) <= LOSS_RTOL:
        raise SmokeError(f"data-parallel losses disagree: {dp} vs {one}")
    return rel


def run_four_chips() -> dict:
    device = phase_device()
    if device["count"] != 4:
        raise SmokeError(f"--chips 4 needs four devices, found "
                         f"{device['count']}")
    phase_data_parallel("fd-tnn-lm-wt103", DP_STEPS, SEQ, BATCH,
                        extra=DP_OPT)
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): every one-chip phase; 4: only the "
                         "data-parallel train check across four chips")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: no repro package under {ROOT}/src; run this "
              "script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import compile_cache
    _say(f"compile cache: {compile_cache.configure()}")
    t0 = time.perf_counter()
    try:
        device = run_four_chips() if args.chips == 4 else run_one_chip()
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
