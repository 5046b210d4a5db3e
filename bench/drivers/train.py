"""Training cells: the launcher's own wiring, ``repro.launch.train.run``
(step jit, ``put_batch``, the Trainer and its byte reader over a corpus
written from the seed), watched from outside.

One object throughout: the launcher builds the step and its state once;
its first steps are the check's and the warm-up's, and the same loop then
runs the window. The harness sees the loop through two seams the program
offers: the launcher's ``tracer`` hook, which marks each step's end after
a device sync, and a subclass of the Trainer whose per-step method calls
the original and then records what the check needs (the batches of the
first steps, the norms of Adam's first moment after step 1 and of the
parameters' change over the check steps). The window opens at the end of
the last warm-up step and closes at the end of the last step that
finishes inside ``--seconds``; the Trainer's own SIGINT handler then
stops the loop at the next step boundary. With ``--trace 1`` a few more
steps run under the profiler after the window.
"""
from __future__ import annotations

import contextlib
import importlib
import os
import signal
import time

from bench import check, common, traffic
from bench.trace import Capture, Phase, op_names

#: training steps whose results the check compares
CHECK_STEPS = 3


def norms_fn(diff: bool):
    """Jitted per-leaf norms (per layer for layer-stacked leaves) of a
    parameter tree, or of the difference of two."""
    import jax
    import jax.numpy as jnp

    def one(path, x):
        x = x.astype(jnp.float32)
        axes = (tuple(range(1, x.ndim)) if check.leaf_name(path)
                .startswith("blocks/") else None)
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))

    if diff:
        return jax.jit(lambda a, b: jax.tree_util.tree_map_with_path(
            one, jax.tree.map(lambda p, q: p - q, a, b)))
    return jax.jit(lambda a: jax.tree_util.tree_map_with_path(one, a))


class Recorder:
    """The launcher's tracer (``begin``/``end`` around each step call) and
    the Trainer hook (``after_step``)."""

    def __init__(self, *, seconds, warm, b1, profile_steps, capture,
                 check_only):
        import jax
        self.seconds = seconds
        self.warm = warm
        self.b1 = b1
        self.profile_steps = profile_steps
        self.capture = capture
        self.phase = Phase(capture)
        self.check_only = check_only
        self.ends = {}
        self.t0 = None            # window opens: end of the last warm step
        self.last = None          # last step that ended inside the window
        self.closed = False
        self.prof_from = None
        self.batches = []
        self.jitted = None        # the launcher's jitted train step
        self.arg_shapes = None    # its (state, batch) shapes and shardings
        self.grad_norms = None
        self.update_norms = None
        self.compiles = 0         # compile or cache-load events in the window
        self._p0 = None
        self._ann = None
        self._norms = norms_fn(False)
        self._diff_norms = norms_fn(True)
        self._stopped = False

        def listen(event, duration, **_):
            if self.t0 is not None and not self.closed and common.is_compile(event):
                self.compiles += 1
        jax.monitoring.register_event_duration_secs_listener(listen)

    # -- tracer protocol (repro.launch.train.run)
    def begin(self, name, step=None, **_):
        self._ann = self.phase(name)
        self._ann.__enter__()

    def end(self, name, step=None, **_):
        now = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self.ends[step] = now
        if step == self.warm - 1:
            self.t0 = now
            if self.check_only:
                self._stop()
            return
        if self.t0 is None or step < self.warm:
            return
        if not self.closed:
            if now - self.t0 <= self.seconds:
                self.last = step
                return
            self.closed = True
            if self.capture is None:
                self._stop()
            else:
                self.capture.start()
                self.prof_from = step
            return
        if (self.prof_from is not None
                and step == self.prof_from + self.profile_steps):
            self.capture.stop()
            self._stop()

    def _stop(self):
        if not self._stopped:
            self._stopped = True
            signal.raise_signal(signal.SIGINT)   # Trainer: stop at boundary

    # -- Trainer hook
    def after_step(self, step, state_in, out, batch):
        import jax
        import numpy as np
        if step >= CHECK_STEPS:
            return
        if step == 0:
            self.arg_shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding),
                (state_in, batch))
        self.batches.append((np.asarray(batch["tokens"]),
                             np.asarray(batch["labels"])))
        new_state = out[0]
        if step == 0:
            self._p0 = state_in["params"]
            mu = self._norms(new_state["opt"].mu)
            self.grad_norms = check.split_leaves(
                mu, lambda a: float(a) / (1.0 - self.b1))
        if step == CHECK_STEPS - 1:
            d = self._diff_norms(new_state["params"], self._p0)
            self.update_norms = check.split_leaves(d, float)
            self._p0 = None


@contextlib.contextmanager
def watched(train_mod, rec: Recorder):
    """Run the launcher with a Trainer that reports each step to ``rec``
    after doing exactly what the Trainer does, and with host annotations
    around its data reads (for the breakdown's idle gaps)."""
    from repro.obs import compilewatch
    from repro.runtime import trainer as trainer_mod
    base, base_batch_at = train_mod.Trainer, trainer_mod.batch_at
    base_wrap = compilewatch.CompileWatch.wrap

    def wrap(self, name, fn, **jit_kwargs):
        call = base_wrap(self, name, fn, **jit_kwargs)
        if name == "train_step":
            rec.jitted = call.jitted
        return call

    def batch_at(cfg, step):
        with rec.phase("data"):
            return base_batch_at(cfg, step)

    class BenchTrainer(base):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            put = self.put_batch

            def put_batch(b):
                with rec.phase("put_batch"):
                    return put(b)
            self.put_batch = put_batch

        def _step_with_retry(self, step, state, batch):
            with rec.phase("trainer_step"):
                out = super()._step_with_retry(step, state, batch)
            rec.after_step(step, state, out, batch)
            return out

    train_mod.Trainer, trainer_mod.batch_at = BenchTrainer, batch_at
    compilewatch.CompileWatch.wrap = wrap
    try:
        yield
    finally:
        train_mod.Trainer, trainer_mod.batch_at = base, base_batch_at
        compilewatch.CompileWatch.wrap = base_wrap


def check_optimizer(opt: dict, args) -> None:
    """The launcher's AdamW has to be the one the traffic file states."""
    from repro.optim import adamw
    have = adamw.OptConfig(lr=args.lr, warmup_steps=args.warmup,
                           total_steps=args.steps)
    bad = {k: (getattr(have, k), v) for k, v in opt.items()
           if getattr(have, k) != v}
    if bad or have.moments_dtype != "float32" or have.compress_grads:
        raise common.BenchError(f"the launcher's optimizer departs from the "
                                f"traffic file: {bad}")


def run(c: dict, *, seed: int, seconds: float, trace: bool, rehearse: bool,
        t_start: float, devs, check_only: bool = False,
        fault: str | None = None, control=None) -> dict:
    """One training run of cell ``c``. For the limit-setting script:
    ``check_only`` stops after the warm-up steps; ``fault`` plants one of
    :func:`plant_fault`'s faults in the program; ``control`` (a dtype)
    also reads the control, the reference in that precision put in the
    program's place on the same batches."""
    import jax

    from repro.launch import train as train_mod
    from repro.launch.mesh import make_host_mesh

    cfg, t = common.cell_parts(c, rehearse)
    common.program_config(cfg, rehearse)
    ps = common.program_seed(seed)
    opt = t["optimizer"]
    n, b = t["seq_len"], t["global_batch"]
    os.makedirs(common.WORK_DIR, exist_ok=True)
    corpus = os.path.join(common.WORK_DIR, f"corpus-{os.getpid()}.bin")
    argv = ["--arch", cfg["arch"], "--steps", str(opt["total_steps"]),
            "--seq-len", str(n), "--global-batch", str(b),
            "--lr", str(opt["lr"]), "--warmup", str(opt["warmup_steps"]),
            "--data", "bytes", "--data-path", corpus, "--seed", str(ps)]
    if rehearse:
        argv.append("--smoke")
    args = train_mod.parse_args(argv)
    check_optimizer(opt, args)
    cap = (Capture(os.path.join(common.WORK_DIR, f"trace-{os.getpid()}"))
           if trace else None)
    rec = Recorder(seconds=seconds, warm=t["warm_steps"], b1=opt["b1"],
                   profile_steps=t["profile_steps"], capture=cap,
                   check_only=check_only)
    try:
        traffic.write_corpus(corpus, ps, t["corpus_bytes"])
        with contextlib.ExitStack() as stack:
            stack.enter_context(watched(train_mod, rec))
            if fault is not None:
                stack.enter_context(plant_fault(fault))
            trainer, watch, _ = train_mod.run(
                args, mesh=make_host_mesh(devs), tracer=rec)
    finally:
        if os.path.exists(corpus):
            os.remove(corpus)
        if cap is not None:
            cap.finish()
    device = common.device_record(devs)
    losses = [float(m["loss"]) for m in trainer.metrics_history[:CHECK_STEPS]]
    out = {"device": device, "trace": cap.trace if cap else None}
    if not check_only:
        if rec.last is None:
            raise common.BenchError(
                f"no whole step finished inside the {seconds} s window")
        steps = rec.last - rec.warm + 1
        wall = rec.ends[rec.last] - rec.t0
        step_s = trainer.step_seconds[rec.warm:rec.last + 1]
        tokens = steps * b * n
        out["window"] = {
            "steps": steps, "seconds": wall, "tokens": tokens,
            "tokens_per_s": tokens / wall, "seq_len": n, "batch": b,
            "step_seconds_mean": sum(step_s) / len(step_s),
            "traced_steps": rec.profile_steps if cap else 0,
            "compiles_in_window": rec.compiles,
            "train_step_compiles": watch.count("train_step")}
        gaps = [rec.ends[s] - rec.ends[s - 1]
                for s in range(rec.warm, rec.last + 1)]
        common.say(f"window: {steps} steps in {wall:.4f} s; step call mean "
                   f"{out['window']['step_seconds_mean']:.4f} s; step to "
                   f"step min {min(gaps):.4f} median "
                   f"{common.percentile(gaps, 50):.4f} max {max(gaps):.4f} s")
        if rec.compiles:
            raise common.BenchError(
                f"{rec.compiles} compile events inside the window")
        if cap is not None:
            # the step's optimised HLO, which names the scope of each op
            # the trace shows; after the window, from the compile cache
            compiled = rec.jitted.lower(*rec.arg_shapes).compile()
            out["hlo"] = compiled.as_text()
            ma = compiled.memory_analysis()
            if ma is not None:
                common.say(
                    f"train step memory: arguments "
                    f"{ma.argument_size_in_bytes}, outputs "
                    f"{ma.output_size_in_bytes}, temporaries "
                    f"{ma.temp_size_in_bytes}, aliased "
                    f"{ma.alias_size_in_bytes}")
            out["breakdown"] = cap.trace.breakdown(
                labels=op_names(out["hlo"]))
        out["e2e"] = {"train_tokens_per_s": tokens / wall,
                      "setup_s": rec.t0 - t_start}
        out["attempted"], out["failed"] = steps, 0
    del trainer
    ref_mod = importlib.import_module(f"bench.references.{cfg['reference']}")
    with jax.default_matmul_precision("highest"):
        ref = ref_mod.train_readings(cfg, opt, ps, rec.batches)
        ctrl = (ref_mod.train_readings(cfg, opt, ps, rec.batches,
                                       dtype=control)
                if control is not None else None)
    prog = {"losses": losses, "grad_norms": rec.grad_norms,
            "update_norms": rec.update_norms}
    out["numbers"] = check.train_numbers(prog, ref)
    if ctrl is not None:
        out["control_numbers"] = check.train_numbers(
            {"losses": ctrl["losses"],
             "grad_norms": check.host_norms(ctrl["grad_first"]),
             "update_norms": check.host_diff_norms(ctrl["params_last"],
                                                   ctrl["params_first"])},
            ref)
    out["cfg"], out["ps"], out["opt"] = cfg, ps, opt
    out["batches"] = rec.batches
    return out


@contextlib.contextmanager
def plant_fault(kind: str):
    """Break the timed path underneath, for the tests and readings that
    show the check catches it: ``frozen`` returns the state unchanged
    from every step; ``half_batch`` computes the step on the first half
    of the rows, the mean taken over them."""
    from repro.launch import steps as steps_mod
    base = steps_mod.StepBuilder.make_train_step

    def make(self):
        step = base(self)
        if kind == "frozen":
            def frozen(state, batch):
                _, metrics = step(state, batch)
                return state, metrics
            return frozen
        if kind == "half_batch":
            def half(state, batch):
                h = batch["tokens"].shape[0] // 2
                return step(state, {k: v[:h] for k, v in batch.items()})
            return half
        raise ValueError(f"unknown fault {kind!r}")

    steps_mod.StepBuilder.make_train_step = make
    try:
        yield
    finally:
        steps_mod.StepBuilder.make_train_step = base
