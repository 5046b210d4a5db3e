"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Wires StepBuilder (jit'd train_step with NamedShardings) + data pipeline +
fault-tolerant Trainer runtime. On this CPU container it runs real training
at smoke scale (--smoke); on a TPU fleet the same file is the per-host
entrypoint (jax.distributed.initialize is called when JAX_COORDINATOR is
set).

XLA flags recorded here for the TPU target (collective/compute overlap is
XLA's latency-hiding scheduler; we enable aggressive async collectives):

    --xla_tpu_enable_async_collective_fusion=true
    --xla_tpu_enable_async_collective_fusion_fuse_all_gather=true
    --xla_tpu_overlap_compute_collective_tc=true
    --xla_enable_async_all_gather=true
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.configs import get_config, reduce_for_smoke
from repro.data.pipeline import DataConfig
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import StepBuilder
from repro.optim import adamw
from repro.runtime.trainer import Trainer, TrainerConfig

TPU_XLA_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
    "--xla_enable_async_all_gather=true")


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--smoke", action="store_true",
                    help="reduce the config to CPU scale")
    ap.add_argument("--mixer", default="",
                    choices=["", "tno", "ski", "fd"],
                    help="override the token mixer with a paper variant")
    ap.add_argument("--data", default="synthetic",
                    choices=["synthetic", "bytes", "lra_match"])
    ap.add_argument("--data-path", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--metrics-file", default=None, metavar="PATH",
                    help="dump the obs metrics registry on exit "
                         "(.json = JSON dump, anything else = Prometheus "
                         "text exposition); also installs the registry as "
                         "the process default so kernel dispatch / compile "
                         "watchdog counters land in it (same contract as "
                         "launch/serve.py)")
    ap.add_argument("--trace-file", default=None, metavar="PATH",
                    help="stream train_step span events to PATH as JSONL "
                         "and write a Chrome trace_event export "
                         "(PATH + '.chrome.json') on exit")
    return ap.parse_args(argv)


def run(args, mesh=None, tracer=None):
    """Train as the parsed ``args`` say: config, mesh (default: every
    device of this host, data-parallel), StepBuilder and Trainer. Returns
    (trainer, watch, seconds): ``trainer.metrics_history`` and
    ``trainer.step_seconds`` hold every step, ``watch`` counts the
    train_step compiles."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    if args.mixer:
        import dataclasses
        cfg = dataclasses.replace(cfg, mixer_override=args.mixer)

    if mesh is None:
        mesh = (make_production_mesh() if args.production_mesh
                else make_host_mesh())
    opt_cfg = adamw.OptConfig(lr=args.lr, warmup_steps=args.warmup,
                              total_steps=args.steps)
    sb = StepBuilder(cfg, mesh, opt_cfg=opt_cfg)

    data_cfg = DataConfig(
        vocab=cfg.vocab, seq_len=args.seq_len,
        global_batch=args.global_batch, seed=args.seed, kind=args.data,
        path=args.data_path,
        host_id=jax.process_index(), num_hosts=jax.process_count())

    state_sh = sb.state_shardings()
    # compile watchdog over the trainer's jit entry point: exactly one
    # trace is expected for the whole run (the batch/seq shapes are
    # fixed); a retrace mid-run means shape churn and shows up as
    # repro_compiles_total{fn="train.train_step"} > 1 plus a warning
    from repro.obs import compilewatch as obs_compile
    watch = obs_compile.CompileWatch(prefix="train.")
    watch.expect("train_step", 1)
    train_step = watch.wrap("train_step", sb.make_train_step(),
                            in_shardings=(state_sh, None),
                            out_shardings=(state_sh, None))
    if tracer is not None:
        import itertools
        inner_step, counter = train_step, itertools.count()

        def train_step(state, batch):
            i = next(counter)
            tracer.begin("train_step", step=i)
            out = inner_step(state, batch)
            # sync before ending the span so the duration is device time,
            # not dispatch time (the Trainer syncs on the loss right
            # after anyway — this costs nothing extra)
            jax.block_until_ready(out[1])
            tracer.end("train_step", step=i)
            return out

    from jax.sharding import NamedSharding, PartitionSpec as P

    def put_batch(host_batch):
        def put(v):
            v = np.asarray(v)
            sh = NamedSharding(
                mesh, P(sb.rules.data_axes, *([None] * (v.ndim - 1))))
            return jax.device_put(v, sh)
        return {k: put(v) for k, v in host_batch.items()}

    tcfg = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every)
    trainer = Trainer(tcfg, train_step, data_cfg, put_batch=put_batch)

    with mesh:
        state = sb.init_state(jax.random.PRNGKey(args.seed))
        state = jax.device_put(state, state_sh)
        state, start = trainer.try_restore(state, shardings=state_sh)
        t0 = time.time()
        state, end = trainer.run(state, start)
        dt = time.time() - t0
    return trainer, watch, dt


def main(argv=None):
    args = parse_args(argv)

    if os.environ.get("JAX_COORDINATOR"):
        jax.distributed.initialize()           # multi-host fleet entry
    compile_cache.configure()

    from repro.obs import metrics as obs_metrics
    from repro.obs import tracing as obs_tracing
    reg = None
    if args.metrics_file is not None:
        reg = obs_metrics.Registry()
        # process default too: backend dispatch counters, the StepBuilder
        # compile watchdog, and the Trainer's own counters all report
        # into the same dump (parity with launch/serve.py)
        obs_metrics.set_default_registry(reg)
    tracer = (obs_tracing.Tracer(args.trace_file)
              if args.trace_file is not None else None)
    if tracer is not None:
        obs_tracing.set_default_tracer(tracer)

    trainer, watch, dt = run(args, tracer=tracer)
    steps_done = max(len(trainer.step_seconds), 1)
    final = (trainer.metrics_history[-1] if trainer.metrics_history
             else {})
    print(f"[train] {steps_done} steps in {dt:.1f}s "
          f"({steps_done / dt:.2f} it/s); final metrics: "
          f"{ {k: float(v) for k, v in final.items()} }")
    if watch.count("train_step") > 1:
        print(f"[train] WARNING: train_step retraced "
              f"{watch.count('train_step')}x (expected 1 compile)")
    if tracer is not None:
        tracer.close()
        chrome = args.trace_file + ".chrome.json"
        obs_tracing.write_chrome(tracer.events, chrome)
        print(f"[train] trace: {args.trace_file} (JSONL), "
              f"{chrome} (Perfetto)")
    if reg is not None and args.metrics_file is not None:
        if args.metrics_file.endswith(".json"):
            reg.dump_json(args.metrics_file)
        else:
            reg.dump_prometheus(args.metrics_file)
        print(f"[train] metrics: {args.metrics_file}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
