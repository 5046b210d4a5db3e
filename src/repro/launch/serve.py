"""Serving launcher: batched prefill + decode loop.

``python -m repro.launch.serve --arch <id> --smoke --prompt-len 32
--gen-len 32 --batch 4`` runs a real generate loop on CPU; on TPU the same
file serves with the production mesh (KV caches sequence-sharded over
`model`, batch over `data` — flash-decoding layout, DESIGN §5).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduce_for_smoke
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import StepBuilder
from repro.models import serving


def generate(sb: StepBuilder, params, prompt, gen_len: int, *,
             temperature: float = 0.0, seed: int = 0,
             chunked_prefill: bool | None = None,
             max_len: int | None = None):
    """prompt: (b, p) int32. Greedy (or sampled) decode of gen_len tokens.

    Prefill: FD-streaming archs consume the prompt in C-token blocks
    through the overlap-save machinery (serving.decode_chunk — one rfft
    per block instead of C sequential steps); any remainder, and every
    other mixer family, is teacher-forced token-by-token. ``None`` (the
    default) auto-detects; False forces token-by-token.

    ``max_len`` sizes the decode cache (default: exactly p + gen_len).
    The FD/TNO kernel realisation depends on the cache length (the RPE
    spectrum is evaluated on the rfft grid of that length), so comparing
    against the continuous-batching engine token-for-token requires the
    same length bucket — pass the engine's max_len here."""
    cfg = sb.cfg
    b, p = prompt.shape
    if max_len is None:
        max_len = p + gen_len
    elif max_len < p + gen_len:
        raise ValueError(f"max_len={max_len} < prompt {p} + gen {gen_len}")
    cache = serving.init_cache(cfg, b, max_len, params=params)
    step = sb.serve_step_jit()

    key = jax.random.PRNGKey(seed)
    out = [prompt]

    def pick(logits):
        nonlocal key
        if temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(
                sub, logits[:, -1] / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits[:, -1], axis=-1)
        return jnp.minimum(nxt, cfg.vocab - 1).astype(jnp.int32)[:, None]

    pos = 0
    logits = None
    supported = serving.supports_chunked_prefill(cfg, cache)
    if chunked_prefill and not supported:
        # an explicit True must not silently run the wrong machinery
        # (non-streaming cache, or non-fd layers decode_chunk can't serve)
        raise ValueError(
            "chunked_prefill=True but the arch/cache does not support it "
            f"(arch {cfg.name}: all mixers must be streaming fd layers)")
    if chunked_prefill is None:
        chunked_prefill = supported
    if chunked_prefill:
        c = serving.stream_block_of(cache)
        chunk_step = sb.chunk_step_jit()
        while pos + c <= p:                       # whole prompt blocks
            logits, cache = chunk_step(
                params, {"tokens": prompt[:, pos:pos + c]}, cache,
                jnp.int32(pos))
            pos += c
    end = p + gen_len
    while pos < end - 1:
        if pos < p:
            tok = prompt[:, pos:pos + 1]          # teacher-forced prefill
        else:
            tok = pick(logits)
            out.append(tok)
        logits, cache = step(params, {"tokens": tok}, cache, jnp.int32(pos))
        pos += 1
    if gen_len > 0:
        out.append(pick(logits))
    return jnp.concatenate(out, axis=1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy decode (the default); > 0 samples — "
                         "both modes work solo and with --engine "
                         "(per-slot RNG lanes)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="engine mode: truncate sampling to the k most "
                         "likely tokens (0 = full distribution; requires "
                         "--temperature > 0)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine: --batch requests "
                         "through S decode slots (repro.serving_engine)")
    ap.add_argument("--slots", type=int, default=None,
                    help="engine decode slots (default REPRO_ENGINE_SLOTS)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="engine mode: seeded FaultInjector chaos run "
                         "(deterministic prefill/decode/callback faults; "
                         "faulted requests end in explicit error outcomes, "
                         "the rest are unaffected)")
    ap.add_argument("--deadline", type=float, default=None, metavar="SEC",
                    help="engine mode: per-request TTL in seconds "
                         "(watchdog evicts expired slots)")
    ap.add_argument("--queue-cap", type=int, default=None,
                    help="engine mode: bounded request queue "
                         "(admission rejects with QueueFull when full)")
    ap.add_argument("--metrics-file", default=None, metavar="PATH",
                    help="dump the obs metrics registry on exit "
                         "(.json = JSON dump, anything else = Prometheus "
                         "text exposition); also installs the registry as "
                         "the process default so kernel dispatch counters "
                         "land in it")
    ap.add_argument("--trace-file", default=None, metavar="PATH",
                    help="engine mode: stream request span events to PATH "
                         "as JSONL and write a Chrome trace_event export "
                         "(PATH + '.chrome.json', Perfetto-loadable) on "
                         "exit")
    args = ap.parse_args(argv)
    if not args.engine and (args.chaos is not None
                            or args.deadline is not None
                            or args.queue_cap is not None):
        ap.error("--chaos/--deadline/--queue-cap require --engine "
                 "(the supervised scheduler owns those knobs)")
    if args.trace_file is not None and not args.engine:
        ap.error("--trace-file requires --engine (request spans are "
                 "emitted by the supervised scheduler)")
    if args.temperature < 0:
        ap.error(f"--temperature {args.temperature} must be >= 0")
    if args.top_k < 0:
        ap.error(f"--top-k {args.top_k} must be >= 0")
    if args.top_k > 0 and args.temperature <= 0:
        # greedy decode ignores top-k; a silently inert knob is worse
        # than a loud one
        ap.error("--top-k requires --temperature > 0 "
                 "(greedy decode never consults it)")
    if args.top_k > 0 and not args.engine:
        ap.error("--top-k requires --engine (the solo path samples the "
                 "full distribution)")

    compile_cache.configure()
    from repro.obs import metrics as obs_metrics
    from repro.obs import tracing as obs_tracing
    reg = None
    if args.metrics_file is not None:
        reg = obs_metrics.Registry()
        # process default too: backend dispatch counters and any engine
        # built without an explicit registry report into the same dump
        obs_metrics.set_default_registry(reg)
    tracer = (obs_tracing.Tracer(args.trace_file)
              if args.trace_file is not None else None)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    sb = StepBuilder(cfg, mesh)

    with mesh:
        from repro.nn.params import unbox
        from repro.models.transformer import init_model
        params, _ = unbox(init_model(jax.random.PRNGKey(args.seed), cfg))
        rng = np.random.default_rng(args.seed)
        prompt = jnp.asarray(
            rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)),
            jnp.int32)
        if args.engine:
            from repro.serving_engine import (Engine, FaultInjector, Request,
                                              Scheduler)
            eng = Engine(cfg, params, slots=args.slots,
                         max_len=args.prompt_len + args.gen_len,
                         temperature=args.temperature, top_k=args.top_k)
            injector = None
            if args.chaos is not None:
                injector = FaultInjector(seed=args.chaos, rates={
                    "prefill": 0.15, "decode": 0.02, "callback": 0.1})
            sched = Scheduler(eng, injector=injector,
                              default_deadline=args.deadline,
                              queue_cap=args.queue_cap,
                              metrics=reg, tracer=tracer,
                              log=print if args.chaos is not None else None)
            for i in range(args.batch):
                sched.submit(Request(uid=f"req{i}",
                                     prompt=np.asarray(prompt[i]),
                                     max_new=args.gen_len,
                                     seed=args.seed + i))
            t0 = time.time()
            results, _ = sched.run()
            dt = time.time() - t0
            n_new = sum(len(v) for v in results.values())
            by_status = {}
            for out in sched.outcomes.values():
                by_status[out.status] = by_status.get(out.status, 0) + 1
            ok_uid = next((u for u, o in sched.outcomes.items()
                           if o.status == "ok"), None)
            mode = ("greedy" if args.temperature == 0 else
                    f"T={args.temperature}"
                    + (f"/top{args.top_k}" if args.top_k else ""))
            print(f"[serve] engine({eng.slots} slots, {mode}) generated "
                  f"{n_new} tokens in {dt:.2f}s ({n_new / dt:.1f} tok/s); "
                  f"steps={sched.steps} prefills={sched.prefills} "
                  f"(packed={sched.packed_prefills}) "
                  f"retries={sched.retries}; outcomes={by_status}; "
                  f"sample: "
                  f"{results[ok_uid][:16] if ok_uid else '(none ok)'}")
            if args.chaos is not None and injector is not None:
                print(f"[serve] chaos(seed={args.chaos}): "
                      f"{injector.fired} faults fired; log={injector.log}")
            if tracer is not None:
                tracer.close()
                chrome = args.trace_file + ".chrome.json"
                obs_tracing.write_chrome(tracer.events, chrome)
                print(f"[serve] trace: {args.trace_file} (JSONL), "
                      f"{chrome} (Perfetto)")
            _dump_metrics(reg, args.metrics_file)
            # a request that failed (a kernel that did not compile, a
            # non-finite guard) must fail the run; only a chaos run
            # expects error outcomes
            failed = {s: c for s, c in by_status.items() if s != "ok"}
            if failed and args.chaos is None:
                print(f"[serve] FAILED: outcomes {failed}")
                return 1
            return 0
        t0 = time.time()
        toks = generate(sb, params, prompt, args.gen_len,
                        temperature=args.temperature, seed=args.seed)
        toks.block_until_ready()
        dt = time.time() - t0
    n_new = args.batch * args.gen_len
    print(f"[serve] generated {n_new} tokens in {dt:.2f}s "
          f"({n_new / dt:.1f} tok/s); sample row: {np.asarray(toks[0])[:16]}")
    _dump_metrics(reg, args.metrics_file)
    return 0


def _dump_metrics(reg, path):
    if reg is None or path is None:
        return
    if path.endswith(".json"):
        reg.dump_json(path)
    else:
        reg.dump_prometheus(path)
    print(f"[serve] metrics: {path}")


if __name__ == "__main__":
    raise SystemExit(main())
