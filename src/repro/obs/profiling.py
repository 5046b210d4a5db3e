"""``jax.profiler`` hooks around serving and training regions.

Set ``REPRO_PROFILE_DIR=/path`` and the scheduler and the trainer
bracket their run loops in a ``jax.profiler`` trace session writing
TensorBoard-loadable protos there. Inside the loops, :func:`annotation`
spans (prefill and decode waves, the trainer's batch read, batch
placement, state copy and step) put the host's work on the profiler's
own clock, beside the device's ops. A span records whenever some
profiler session is active, whoever started it (this module's
``session``, a benchmark's capture, TensorBoard's on-demand capture);
with none active it records nothing and costs one small object.

The profiler can genuinely fail to start (no profiler plugin in a
stripped CPU wheel, a second concurrent session, a read-only dir);
``session`` degrades to a logged warning instead of taking down the
serving loop — observability must never become the outage."""
from __future__ import annotations

import contextlib
import os

from repro.obs import log as obs_log

_ENV_DIR = "REPRO_PROFILE_DIR"


def profile_dir() -> str | None:
    v = os.environ.get(_ENV_DIR)
    return v or None


@contextlib.contextmanager
def session(name: str = "run"):
    """Bracket a region in a ``jax.profiler`` trace when
    ``REPRO_PROFILE_DIR`` is set; no-op otherwise. Never raises."""
    d = profile_dir()
    if d is None:
        yield False
        return
    import jax
    started = False
    try:
        jax.profiler.start_trace(d)
        started = True
        obs_log.get_logger("obs").info(
            f"profiler session '{name}' -> {d}")
    except Exception as e:  # noqa: BLE001 — never fail the serving loop
        obs_log.get_logger("obs").warning(
            f"profiler session '{name}' failed to start: {e!r}")
    try:
        yield started
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                obs_log.get_logger("obs").warning(
                    f"profiler stop failed: {e!r}")


def annotation(name: str):
    """A named span: a band on the host timeline of any active profiler
    session, and nothing without one. It takes no keyword arguments, so
    the name reads back from the trace as written; a step's spans are
    told apart by their order on the host thread."""
    import jax
    return jax.profiler.TraceAnnotation(name)


__all__ = ["profile_dir", "session", "annotation"]
