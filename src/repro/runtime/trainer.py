"""Training runtime: fault-tolerant step loop.

Responsibilities (DESIGN §5 "1000+-node posture"):

* **Checkpoint/restart** — async manifest checkpoints every
  ``ckpt_every`` steps; on start, auto-resume from the latest committed
  step (params + optimizer state + data cursor).
* **Preemption** — SIGTERM/SIGINT triggers a final synchronous
  checkpoint, then a clean exit (the cluster scheduler restarts the job
  and it resumes exactly where it stopped).
* **Step retry** — transient failures (injected in tests via
  ``failure_hook``; on real fleets: ICI timeouts, host OOM) retry the
  same step up to ``max_retries`` times. The data pipeline is stateless
  so a retried step re-reads the identical batch. A ``train_step`` that
  donates its state consumes the input buffers, so a retry must never
  replay on them: while the step donates, or before any step has
  finished, the state is copied to the host before each step
  (``undonated_retry_copy``) and a retry rebuilds it from that copy,
  each leaf with its own sharding. Whether the step donates is read off
  the step itself: after each successful call, a deleted input leaf
  means it does. A step that does not donate leaves its input alive
  whatever happens in the call, so later steps take no copy and a retry
  starts from the live state.
* **Straggler monitor** — per-step wall time EMA; steps slower than
  ``straggler_factor``× the EMA are logged with their step index. On a
  real fleet this feeds the scheduler's hot-spare swap; here it is a
  hook + a counter observable by tests.
* **NaN guard** — non-finite loss aborts the step and retries (on real
  hardware this catches SDC / chip faults; persistent NaN raises).
* **Dispatch banner** — ``run()`` logs the kernel backend policy
  (platform / use_pallas / pallas_grad, ``backend.describe()``) once at
  startup: a training run silently on the wrong path (e.g. reference
  kernels on TPU, or ``REPRO_PALLAS_GRAD=0`` left over from a debugging
  session) is visible in the first line of the step log.
"""
from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import manifest as ckpt
from repro.data.pipeline import DataConfig, batch_at
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs import profiling as obs_prof


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 100
    keep_ckpts: int = 3
    max_retries: int = 2
    straggler_factor: float = 3.0
    ema_alpha: float = 0.1
    log_every: int = 10
    # A train_step that donates its state and then fails leaves `state`
    # invalidated, so a naive retry replays the step on dead arrays. When
    # retries are enabled this keeps a host-side copy of the state on the
    # first step and then on every step while the step is seen to donate,
    # and rebuilds from it on retry (cost: one host transfer per such
    # step; a step that does not donate pays it once). False turns the
    # copy off, for runs that accept retry-unsafety.
    undonated_retry_copy: bool = True


class StragglerMonitor:
    """EMA step-time outlier detector."""

    def __init__(self, factor: float, alpha: float):
        self.factor = factor
        self.alpha = alpha
        self.ema: Optional[float] = None
        self.flagged: list = []

    def observe(self, step: int, dt: float) -> bool:
        is_straggler = self.ema is not None and dt > self.factor * self.ema
        if is_straggler:
            self.flagged.append((step, dt, self.ema))
        # EMA excludes outliers so one straggler doesn't mask the next
        if not is_straggler:
            self.ema = dt if self.ema is None else (
                (1 - self.alpha) * self.ema + self.alpha * dt)
        return is_straggler


class Trainer:
    def __init__(self, cfg: TrainerConfig, train_step: Callable,
                 data_cfg: DataConfig, *,
                 put_batch: Optional[Callable] = None,
                 failure_hook: Optional[Callable[[int, int], None]] = None,
                 log: Optional[Callable[[str], None]] = None,
                 metrics=None):
        """``train_step(state, batch) -> (state, metrics)``, typically
        jit'd; it may donate its state or not, and the Trainer learns
        which from the first step (``undonated_retry_copy``).
        ``put_batch(host_batch) -> device batch``
        places host numpy onto the mesh (identity by default).
        ``failure_hook(step, attempt)`` may raise to inject failures.
        ``metrics`` is an obs registry (default: the process registry —
        a no-op unless ``REPRO_METRICS``); ``log`` defaults to the obs
        logger (``REPRO_LOG_LEVEL``; quiet under pytest)."""
        self.cfg = cfg
        self.train_step = train_step
        self.data_cfg = data_cfg
        self.put_batch = put_batch or (lambda b: b)
        self.failure_hook = failure_hook
        self.log = log or obs_log.get_logger("trainer").info
        self.metrics = (metrics if metrics is not None
                        else obs_metrics.default_registry())
        m = self.metrics
        self._m_steps = m.counter(
            "repro_train_steps_total", "training steps completed")
        self._m_retries = m.counter(
            "repro_train_retries_total", "training step retries")
        self._m_copies = m.counter(
            "repro_train_state_copies_total",
            "host copies of the train state taken for retry")
        self._m_stragglers = m.counter(
            "repro_train_stragglers_total", "steps flagged as stragglers")
        self._m_ckpts = m.counter(
            "repro_train_checkpoints_total",
            "checkpoint saves issued", ("mode",))
        self._m_step_s = m.histogram(
            "repro_train_step_seconds", "train_step wall time")
        self._m_loss = m.gauge(
            "repro_train_loss", "last finite training loss")
        self.monitor = StragglerMonitor(cfg.straggler_factor, cfg.ema_alpha)
        self.ckpt = (ckpt.AsyncCheckpointer(cfg.ckpt_dir, cfg.keep_ckpts)
                     if cfg.ckpt_dir else None)
        self._preempted = False
        self.metrics_history: list = []
        self.step_seconds: list = []     # wall time of each finished step
        # whether train_step donates its state: None until a step has
        # finished, then whether that step deleted an input leaf
        self._donates: Optional[bool] = None

    # ---------------------------------------------------------- signals
    def _install_signals(self):
        def handler(signum, frame):
            self._preempted = True
            self.log(f"[trainer] signal {signum}: checkpoint-and-exit requested")
        self._old = {s: signal.signal(s, handler)
                     for s in (signal.SIGTERM, signal.SIGINT)}

    def _restore_signals(self):
        for s, h in getattr(self, "_old", {}).items():
            signal.signal(s, h)

    # ------------------------------------------------------------- ckpt
    def _save(self, step: int, state: Any, *, sync: bool = False):
        if self.ckpt is None:
            return
        extra = {"data_step": step}
        if sync:
            self.ckpt.wait()
            host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state)
            ckpt.save(self.cfg.ckpt_dir, step, host, extra=extra)
        else:
            self.ckpt.save_async(step, state, extra=extra)

    def try_restore(self, state_like: Any, shardings: Any = None):
        """Returns (state, start_step) — (state_like, 0) if no checkpoint."""
        if self.cfg.ckpt_dir is None:
            return state_like, 0
        step = ckpt.latest_step(self.cfg.ckpt_dir)
        if step is None:
            return state_like, 0
        state, extra = ckpt.restore(self.cfg.ckpt_dir, state_like,
                                    step=step, shardings=shardings)
        self.log(f"[trainer] restored step {step}")
        return state, int(extra.get("data_step", step))

    # -------------------------------------------------------------- run
    def run(self, state: Any, start_step: int = 0) -> Any:
        from repro.kernels import backend
        self.log(f"[trainer] kernel dispatch: {backend.describe()}")
        self._install_signals()
        prof = obs_prof.session("train")   # no-op unless REPRO_PROFILE_DIR
        prof.__enter__()
        try:
            step = start_step
            while step < self.cfg.total_steps and not self._preempted:
                with obs_prof.annotation("repro.train.read_batch"):
                    host_batch = batch_at(self.data_cfg, step)
                with obs_prof.annotation("repro.train.put_batch"):
                    batch = self.put_batch(host_batch)
                state, metrics = self._step_with_retry(step, state, batch)
                self.metrics_history.append(metrics)
                self._m_steps.inc()
                if self.cfg.log_every and step % self.cfg.log_every == 0:
                    ms = {k: float(v) for k, v in metrics.items()}
                    self.log(f"[trainer] step {step}: {ms}")
                step += 1
                if self.ckpt and step % self.cfg.ckpt_every == 0:
                    self._save(step, state)
                    self._m_ckpts.labels(mode="async").inc()
            if self.ckpt:
                self._save(step, state, sync=True)   # final / preemption save
                self._m_ckpts.labels(mode="sync").inc()
            return state, step
        finally:
            self._restore_signals()
            prof.__exit__(None, None, None)

    def _step_with_retry(self, step: int, state: Any, batch: Any):
        last_err: Optional[BaseException] = None
        guard = self.cfg.max_retries > 0 and self.cfg.undonated_retry_copy
        backup = None
        if guard and self._donates is not False:
            # the step may donate: keep a host-side copy so a retry never
            # reuses buffers a failed attempt invalidated
            with obs_prof.annotation("repro.train.state_copy"):
                backup = jax.tree.map(_host_copy, state)
            self._m_copies.inc()
        like = state
        for attempt in range(self.cfg.max_retries + 1):
            if attempt > 0 and backup is not None:
                state = jax.tree.map(_put_like, backup, like)
            elif attempt > 0 and guard and _any_deleted(state):
                raise RuntimeError(
                    f"step {step}: attempt {attempt - 1} deleted its input "
                    f"state, but earlier steps did not donate it, so no "
                    f"host copy was taken to retry from")
            try:
                if self.failure_hook is not None:
                    self.failure_hook(step, attempt)
                t0 = time.perf_counter()
                # the loss read syncs on the step's device work
                with obs_prof.annotation("repro.train.step"):
                    new_state, metrics = self.train_step(state, batch)
                    loss = metrics.get("loss")
                    loss = None if loss is None else float(loss)
                if loss is not None and not np.isfinite(loss):
                    raise FloatingPointError(f"non-finite loss at step {step}")
                dt = time.perf_counter() - t0
                self.step_seconds.append(dt)
                self._m_step_s.observe(dt)
                if loss is not None:
                    self._m_loss.set(loss)
                if guard:
                    self._donates = _any_deleted(state)
                if self.monitor.observe(step, dt):
                    self._m_stragglers.inc()
                    self.log(f"[trainer] straggler: step {step} took {dt:.3f}s "
                             f"(ema {self.monitor.ema:.3f}s)")
                return new_state, metrics
            except (FloatingPointError, RuntimeError, ValueError) as e:
                last_err = e
                if attempt < self.cfg.max_retries:
                    self._m_retries.inc()
                self.log(f"[trainer] step {step} attempt {attempt} failed: {e}")
        raise RuntimeError(
            f"step {step} failed after {self.cfg.max_retries + 1} attempts"
        ) from last_err


def _any_deleted(tree: Any) -> bool:
    """Whether a leaf of ``tree`` is a deleted array (a donated buffer);
    leaves that cannot be deleted, such as numpy arrays, count as alive."""
    return any(getattr(x, "is_deleted", lambda: False)()
               for x in jax.tree.leaves(tree))


def _host_copy(x: Any) -> np.ndarray:
    """``x`` on the host, in memory of its own. The CPU backend can hand
    back a view of the device buffer itself, and a backup holding that
    view keeps a donating step from consuming the buffer."""
    h = np.asarray(jax.device_get(x))
    return h if h.flags.owndata else h.copy()


def _put_like(host: Any, like: Any) -> Any:
    """``host``, a copy of ``like``, back on ``like``'s devices and sharding."""
    if isinstance(like, jax.Array):
        return jax.device_put(host, like.sharding)
    return jnp.asarray(host)
