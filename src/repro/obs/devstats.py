"""Device-time attribution + memory gauges (ISSUE 10 tentpole §2/§3b).

Three concerns, all off-by-default-cheap like the rest of the obs tier:

* **Kernel regions** — :func:`kernel_region` wraps every dispatch site
  in ``kernels/ops.py`` (the same layer that counts
  ``repro_kernel_dispatch_total``) in a ``jax.named_scope`` so the
  kernel name lands in HLO op metadata (→ XLA/TPU profiler attribution
  on real hardware). It is trace-time only: zero steady state cost
  inside a compiled executable.
* **Attribution** — on a profiled run, :func:`aggregate_chrome` sums
  per-kernel wall seconds out of a Chrome trace (ours or the
  profiler's). On CPU smoke runs — where annotations cannot see device
  time — :func:`attribute_engine` takes the *measured* engine seconds
  (the scheduler's ``repro_decode_step_seconds`` /
  ``repro_prefill_seconds`` histogram sums) and splits them across
  kernel families using the analytic share map from
  :func:`repro.obs.cost.decode_step_cost`. Either path records into
  ``repro_kernel_seconds_total{kernel}`` and a per-kernel
  ``repro_kernel_roofline_frac`` gauge, which ``tools/obs_report.py
  --kernels`` renders.
* **Memory gauges** — :func:`sample_memory` publishes live device
  bytes, DecodeState cache bytes, and the fd ring/spectra slice of the
  cache as gauges; the scheduler samples it every
  ``REPRO_MEM_SAMPLE_EVERY`` steps (0 = off, the default).
"""
from __future__ import annotations

import contextlib
import gzip
import json
import os
from pathlib import Path
from typing import Dict, Iterable, List, Optional

from repro.obs import cost as obs_cost
from repro.obs import metrics as obs_metrics

#: named_scope prefix for kernel regions — the aggregator keys off it
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


# ------------------------------------------------ trace aggregation
def aggregate_chrome(events: Iterable[dict],
                     prefix: str = KERNEL_SCOPE_PREFIX) -> Dict[str, float]:
    """Sum per-kernel seconds from Chrome ``trace_event`` records (the
    profiler's ``*.trace.json``, or our own exporter's output). Handles
    complete events (``X`` with ``dur`` µs) and ``B``/``E`` pairs
    (stacked per (pid, tid, name)). Returns ``{kernel: seconds}`` for
    events whose name starts with ``prefix`` (stripped)."""
    totals: Dict[str, float] = {}
    open_b: Dict[tuple, List[float]] = {}
    for ev in events:
        name = ev.get("name", "")
        if not isinstance(name, str) or not name.startswith(prefix):
            continue
        kernel = name[len(prefix):]
        ph = ev.get("ph")
        if ph == "X":
            totals[kernel] = totals.get(kernel, 0.0) \
                + float(ev.get("dur", 0.0)) * 1e-6
        elif ph == "B":
            key = (ev.get("pid"), ev.get("tid"), kernel)
            open_b.setdefault(key, []).append(float(ev["ts"]))
        elif ph == "E":
            key = (ev.get("pid"), ev.get("tid"), kernel)
            stack = open_b.get(key)
            if stack:
                totals[kernel] = totals.get(kernel, 0.0) \
                    + (float(ev["ts"]) - stack.pop()) * 1e-6
    return totals


def load_profile_traces(profile_dir: str) -> List[dict]:
    """Collect ``traceEvents`` from every ``*.trace.json[.gz]`` under a
    ``jax.profiler`` session directory."""
    events: List[dict] = []
    root = Path(profile_dir)
    for p in sorted(root.rglob("*.trace.json")) + \
            sorted(root.rglob("*.trace.json.gz")):
        try:
            if p.suffix == ".gz":
                with gzip.open(p, "rt") as f:
                    doc = json.load(f)
            else:
                with open(p) as f:
                    doc = json.load(f)
        except (OSError, ValueError):
            continue
        events.extend(doc.get("traceEvents", []))
    return events


def record_kernel_seconds(seconds_by_kernel: Dict[str, float],
                          metrics=None) -> None:
    """Accumulate attributed seconds into
    ``repro_kernel_seconds_total{kernel}``."""
    reg = metrics if metrics is not None else obs_metrics.default_registry()
    m = reg.counter("repro_kernel_seconds_total",
                    "attributed device/engine seconds per kernel family",
                    ("kernel",))
    for kernel, s in seconds_by_kernel.items():
        if s > 0:
            m.labels(kernel=kernel).inc(s)


# ------------------------------------------------------ attribution
def _hist_sum(reg, name: str) -> float:
    m = reg.get(name) if hasattr(reg, "get") else None
    if m is None or getattr(m, "kind", None) != "histogram":
        return 0.0
    with m._lock:
        return sum(ch.sum for ch in m._children.values())


def attribute_engine(engine, metrics, *, drain_s: Optional[float] = None,
                     profile_dir: Optional[str] = None) -> dict:
    """Split measured engine seconds across kernel families and record
    them (tentpole §2's CPU-honest path; acceptance: ≥ 80% of the S=16
    drain accounted for).

    Ground truth seconds come from the scheduler's own histograms —
    ``repro_decode_step_seconds`` + ``repro_prefill_seconds`` sums,
    which time the blocking device calls. When a profiler trace is
    available (``profile_dir``), per-kernel region seconds are used
    directly; otherwise the decode seconds are projected onto families
    by the analytic FLOP shares of one decode step
    (:func:`repro.obs.cost.decode_step_cost` for the engine's arch —
    on CPU, where every family is effectively compute-bound, FLOPs are
    the honest weight). Records ``repro_kernel_seconds_total{kernel}``
    + ``repro_kernel_roofline_frac{kernel}`` and returns::

        {"device_s", "coverage", "rows": [
            {"kernel", "seconds", "frac", "roofline_frac"}, ...]}

    ``coverage`` is device_s / drain_s (None when drain_s not given).
    """
    step_s = _hist_sum(metrics, "repro_decode_step_seconds")
    prefill_s = _hist_sum(metrics, "repro_prefill_seconds")
    device_s = step_s + prefill_s

    by_kernel: Dict[str, float] = {}
    if profile_dir:
        by_kernel = aggregate_chrome(load_profile_traces(profile_dir))
    if not by_kernel and device_s > 0:
        cfg = engine.cfg
        costs = obs_cost.decode_step_cost(cfg, engine.slots, engine.max_len)
        flops_total = sum(c.flops for c in costs.values()) or 1.0
        by_kernel = {k: step_s * (c.flops / flops_total)
                     for k, c in costs.items()}
        if prefill_s > 0:
            # prefill is one fused forward over the prompt — same family
            # mix at n=bucket length; reuse the step shares
            for k, c in costs.items():
                by_kernel[k] = by_kernel.get(k, 0.0) \
                    + prefill_s * (c.flops / flops_total)
    record_kernel_seconds(by_kernel, metrics)

    pk = obs_cost.peaks()
    costs = obs_cost.decode_step_cost(engine.cfg, engine.slots,
                                      engine.max_len)
    # steps executed ≈ decode-step histogram count
    m = metrics.get("repro_decode_step_seconds") if hasattr(
        metrics, "get") else None
    n_steps = 0
    if m is not None and getattr(m, "kind", None) == "histogram":
        with m._lock:
            n_steps = sum(ch.count for ch in m._children.values())
    frac_gauge = metrics.gauge(
        "repro_kernel_roofline_frac",
        "achieved fraction of the roofline bound per kernel family",
        ("kernel",))
    total_s = sum(by_kernel.values()) or 1.0
    rows = []
    for kernel, s in sorted(by_kernel.items(), key=lambda kv: -kv[1]):
        rf = None
        c = costs.get(kernel)
        if c is not None and n_steps > 0 and s > 0:
            rf = obs_cost.achieved_fraction(c.scale(n_steps), s, pk)
            frac_gauge.labels(kernel=kernel).set(rf)
        rows.append({"kernel": kernel, "seconds": s,
                     "frac": s / total_s, "roofline_frac": rf})
    return {"device_s": device_s,
            "coverage": (device_s / drain_s) if drain_s else None,
            "rows": rows}


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
           "aggregate_chrome", "load_profile_traces",
           "record_kernel_seconds", "attribute_engine", "sample_memory",
           "mem_sample_every"]
