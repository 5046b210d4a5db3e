"""Kernel regions and memory gauges.

Two concerns, both off-by-default-cheap like the rest of the obs tier:

* **Kernel regions** — :func:`kernel_region` wraps every dispatch site
  in ``kernels/ops.py`` (the same layer that counts
  ``repro_kernel_dispatch_total``) and the attention core
  (``models/attention.py``) in a ``jax.named_scope`` so the
  kernel name lands in HLO op metadata. The benchmark's trace reader
  (``bench/trace.py``) finds a kernel's device time through these
  scopes, so their names are what the per-kernel metrics read. It is
  trace-time only: zero steady state cost inside a compiled executable.
* **Memory gauges** — :func:`sample_memory` publishes live device
  bytes, DecodeState cache bytes, and the fd ring/spectra slice of the
  cache as gauges; the scheduler samples it every
  ``REPRO_MEM_SAMPLE_EVERY`` steps (0 = off, the default).
"""
from __future__ import annotations

import contextlib
import os
from typing import Dict, Optional

from repro.obs import metrics as obs_metrics

#: named_scope prefix for kernel regions — the benchmark's scope reader
#: keys off it
KERNEL_SCOPE_PREFIX = "repro_kernel."

_ENV_MEM_EVERY = "REPRO_MEM_SAMPLE_EVERY"

#: DecodeState cache leaves that belong to the fd streaming decode path
#: (overlap-save ring + block/tail spectra) — see serving_engine/state.py
FD_STREAM_LEAVES = ("ring", "tail", "uspec_re", "uspec_im")


def mem_sample_every() -> int:
    v = os.environ.get(_ENV_MEM_EVERY)
    if v is None or v == "":
        return 0
    try:
        return max(int(v), 0)
    except ValueError:
        raise ValueError(f"{_ENV_MEM_EVERY}={v!r} is not an int") from None


# ------------------------------------------------------------ regions
@contextlib.contextmanager
def kernel_region(kernel: str):
    """Mark a kernel dispatch site. ``jax.named_scope`` stamps the
    kernel name into the HLO metadata of every op traced inside (the
    XLA profiler then attributes device time to it on real hardware).
    Runs at trace time only — compiled calls never re-enter it, so a
    host-side span here would mark compile time, not kernel time."""
    import jax
    with jax.named_scope(KERNEL_SCOPE_PREFIX + kernel):
        yield


# --------------------------------------------------------- memory gauges
def _path_key_names(path) -> list:
    names = []
    for k in path:
        name = getattr(k, "key", None)
        if name is None:
            name = getattr(k, "name", None)
        if name is not None:
            names.append(str(name))
    return names


def _tree_bytes(tree, names: Optional[tuple] = None) -> int:
    """Sum ``nbytes`` over array leaves; with ``names``, only leaves
    whose pytree path contains one of those dict keys (DecodeState cache
    leaves are keyed by name — see ``state.BATCH_AXIS_FROM_END``)."""
    import jax
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        nb = getattr(leaf, "nbytes", None)
        if nb is None:
            continue
        if names is not None and not any(
                n in names for n in _path_key_names(path)):
            continue
        total += int(nb)
    return total


def sample_memory(metrics=None, state=None, *,
                  reuse: Optional[dict] = None) -> Dict[str, float]:
    """Publish HBM/live-buffer gauges (tentpole §3b): total live device
    bytes (``jax.live_arrays()``, guarded — absent on some backends),
    DecodeState cache bytes, and the fd ring/spectra slice of the cache.
    Returns the sampled values; called from the scheduler loop every
    ``REPRO_MEM_SAMPLE_EVERY`` steps.

    ``reuse`` (a caller-held dict) caches the cache-pytree byte sums:
    the DecodeState cache is fixed-shape for the lifetime of a drain, so
    the pytree walk happens once and later samples republish the cached
    sizes — only the live-array total is re-measured each time."""
    reg = metrics if metrics is not None else obs_metrics.default_registry()
    out: Dict[str, float] = {}
    import jax
    try:
        live = sum(int(getattr(a, "nbytes", 0))
                   for a in jax.live_arrays())
    except Exception:  # noqa: BLE001 — live_arrays is best-effort
        live = 0
    if live:
        reg.gauge("repro_live_device_bytes",
                  "total bytes of live jax arrays").set(live)
        out["repro_live_device_bytes"] = float(live)
    if state is not None:
        cache = getattr(state, "cache", None)
        if cache is not None:
            if reuse is not None and "cache_bytes" in reuse:
                cb, fd = reuse["cache_bytes"], reuse["fd_bytes"]
            else:
                cb = _tree_bytes(cache)
                fd = _tree_bytes(cache, FD_STREAM_LEAVES)
                if reuse is not None:
                    reuse["cache_bytes"], reuse["fd_bytes"] = cb, fd
            reg.gauge("repro_decode_cache_bytes",
                      "DecodeState cache bytes across slots").set(cb)
            out["repro_decode_cache_bytes"] = float(cb)
            if fd:
                reg.gauge("repro_fd_stream_bytes",
                          "fd overlap-save ring + spectra bytes").set(fd)
                out["repro_fd_stream_bytes"] = float(fd)
    return out


__all__ = ["kernel_region", "KERNEL_SCOPE_PREFIX", "FD_STREAM_LEAVES",
           "sample_memory", "mem_sample_every"]
