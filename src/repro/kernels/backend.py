"""Backend selection, block-size autotuning, and ragged-shape policy for
the Pallas kernels.

This module is the single place that decides *how* a kernel runs:

* **Platform detection** — ``platform()`` reports the active JAX backend.
  On TPU the Pallas kernels compile (``interpret=False``); everywhere else
  they run in interpret mode (kernel body executed by the Pallas
  interpreter), and the jnp reference oracles are the default execution
  path (``use_pallas`` resolves to False unless forced).
* **Block sizing** — ``get_blocks`` returns (bn, bd) tile sizes for a
  (kernel, n, d, dtype, platform) key: first from the on-disk autotune
  cache, else (when autotuning is enabled and the inputs are concrete) by
  timing a small candidate sweep, else from a shape-fitted heuristic.
* **Ragged shapes** — ``fit_block`` / ``round_up`` let callers pick tiles
  for n/d that do *not* divide the defaults; kernels zero-pad up to the
  tile multiple and slice the result (zero padding is semantics-preserving
  for every kernel in this package: conv uses zero boundary conditions and
  the interp/gram contractions are linear).

Training-path dispatch (PR 2)
-----------------------------
The Pallas ops carry ``jax.custom_vjp`` rules whose backward passes are
themselves Pallas kernels (transposed siblings of the forwards — see
:mod:`repro.kernels.ski_vjp`), so ``jax.grad`` through the fused SKI
pipeline stays on the kernel path instead of silently requiring the jnp
reference. :func:`resolve_pallas_grad` is the single switch the backward
rules consult at trace time: under "auto" (default) the kernel backward is
used whenever the Pallas forward is; ``REPRO_PALLAS_GRAD=0`` keeps the
Pallas forward but computes cotangents with the jnp reference formulas
(debugging escape hatch / numerical bisection).

Residual/recompute policy: the custom VJPs save only the *inputs* of each
op (plus the per-forward plan already materialised by the caller); no
O(n·r) activation is stored. The pass-1 reduction z = Wᵀx is recomputed
in the backward from the saved x — one extra O(n r d) kernel launch
instead of an (b, r, d) residual held across the whole backward.

Environment knobs (also documented in :mod:`repro.kernels.ops`):

* ``REPRO_USE_PALLAS``    — "1"/"0" force the Pallas/reference path;
  "auto" (default) selects Pallas exactly on TPU.
* ``REPRO_PALLAS_INTERPRET`` — "1"/"0" force interpret/compiled;
  "auto" (default) compiles exactly on TPU.
* ``REPRO_PALLAS_GRAD``   — "1"/"0" force the kernel/reference backward
  under the Pallas forward; "auto" (default) follows the forward path.
* ``REPRO_AUTOTUNE``      — "1" enables the timing sweep on cache miss.
* ``REPRO_AUTOTUNE_CACHE`` — cache file path
  (default ``~/.cache/repro/autotune.json``).

Large-rank SKI dispatch (PR 3)
------------------------------
:func:`ski_rank_variant` is the single policy point that picks how the
fused SKI pipeline applies the r×r inducing Gram:

* ``dense``    — r ≤ 512 (``REPRO_SKI_DENSE_RMAX``) and the (d, r, r)
  dense Gram under the 64 MB budget: the original fused kernel with the
  whole Gram VMEM-resident per d-tile.
* ``windowed`` — 512 < r ≤ 4096 (``REPRO_SKI_WINDOWED_RMAX``): the O(n)
  banded-W kernel streaming (bw, bw) Toeplitz band blocks regenerated
  from coefficients; the band width follows the sequence tile via
  :func:`band_fit` under the ``REPRO_SKI_BAND_MAX`` budget (default 128).
* ``fft``      — beyond the windowed ceiling: the Toeplitz Gram is
  applied by a length-2r rfft/irfft circulant matvec between the two
  kernel passes (O(r log r)); pass 2 is the Gram-free windowed kernel.

The dense form needs the (d, r, r) materialisation (16 GB at r = 8192,
d = 64) — the coefficient-form variants only ever hold (d, 2r-1).
"""
from __future__ import annotations

import json
import os
import threading
import time

import jax

_ENV_BACKEND = "REPRO_USE_PALLAS"
_ENV_INTERPRET = "REPRO_PALLAS_INTERPRET"
_ENV_GRAD = "REPRO_PALLAS_GRAD"
_ENV_AUTOTUNE = "REPRO_AUTOTUNE"
_ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
_ENV_DENSE_RMAX = "REPRO_SKI_DENSE_RMAX"
_ENV_WINDOWED_RMAX = "REPRO_SKI_WINDOWED_RMAX"
_ENV_BAND_MAX = "REPRO_SKI_BAND_MAX"
_ENV_FD_STREAM = "REPRO_FD_STREAM"
_ENV_FD_STREAM_C = "REPRO_FD_STREAM_C"

_FORCED_DEFAULT: bool | None = None     # set_default_use_pallas override
_FORCED_GRAD: bool | None = None        # set_default_pallas_grad override


# ------------------------------------------------------------- dispatch
def platform() -> str:
    """Active JAX backend: "cpu" | "tpu" | "gpu"."""
    return jax.default_backend()


def set_default_use_pallas(flag: bool | None) -> None:
    """Programmatic override of the global default (None = back to auto)."""
    global _FORCED_DEFAULT
    _FORCED_DEFAULT = None if flag is None else bool(flag)


def use_pallas_default() -> bool:
    if _FORCED_DEFAULT is not None:
        return _FORCED_DEFAULT
    v = os.environ.get(_ENV_BACKEND, "auto").lower()
    if v in ("1", "true"):
        return True
    if v in ("0", "false"):
        return False
    return platform() == "tpu"


def resolve_use_pallas(flag) -> bool:
    """Explicit per-call flag wins; None falls back to the global policy."""
    return use_pallas_default() if flag is None else bool(flag)


def resolve_interpret(flag=None) -> bool:
    """Compiled Pallas only on TPU unless explicitly forced."""
    if flag is not None:
        return bool(flag)
    v = os.environ.get(_ENV_INTERPRET, "auto").lower()
    if v in ("1", "true"):
        return True
    if v in ("0", "false"):
        return False
    return platform() != "tpu"


def set_default_pallas_grad(flag: bool | None) -> None:
    """Programmatic override of the backward-path policy (None = auto)."""
    global _FORCED_GRAD
    _FORCED_GRAD = None if flag is None else bool(flag)


def resolve_pallas_grad(flag=None) -> bool:
    """Should a Pallas forward use its Pallas backward kernels?

    Consulted (at trace time) by the ``jax.custom_vjp`` backward rules of
    the Pallas ops. "auto" (default) returns True — the kernel backward
    runs whenever the kernel forward was selected; ``REPRO_PALLAS_GRAD=0``
    (or :func:`set_default_pallas_grad`) swaps in the jnp reference
    cotangent formulas while keeping the Pallas forward, for debugging.
    """
    if flag is not None:
        return bool(flag)
    if _FORCED_GRAD is not None:
        return _FORCED_GRAD
    v = os.environ.get(_ENV_GRAD, "auto").lower()
    if v in ("1", "true"):
        return True
    if v in ("0", "false"):
        return False
    return True


def describe() -> str:
    """One-line dispatch summary (logged by the trainer at startup so a
    silent wrong-path run is visible in the step log)."""
    return (f"platform={platform()} use_pallas={use_pallas_default()} "
            f"interpret={resolve_interpret()} "
            f"pallas_grad={resolve_pallas_grad()} "
            f"ski_variant=(dense<={ski_dense_rank_max()}"
            f"<windowed<={ski_windowed_rank_max()}<fft"
            f"|band<={band_budget()}) "
            f"fd_stream={fd_stream_enabled()}(C={fd_stream_block()})")


def log_describe() -> None:
    """Emit the :func:`describe` banner through the obs logger (one INFO
    line; quiet under pytest / ``REPRO_LOG_LEVEL=WARNING``)."""
    from repro.obs import log as obs_log
    obs_log.banner(describe(), "backend")


# ------------------------------------------------- FD streaming decode
def fd_stream_enabled() -> bool:
    """Serving policy: replace the O(n·d)-per-token hist-replay decode of
    ``fd`` mixers with the overlap-save streaming cache
    (kernels/fd_stream.py). "auto" (default) enables it whenever the
    cache can be built (params available at init); ``REPRO_FD_STREAM=0``
    pins the legacy hist-replay cache (debug / A-B comparison)."""
    v = os.environ.get(_ENV_FD_STREAM, "auto").lower()
    if v in ("1", "true", "on"):
        return True
    if v in ("0", "false", "off"):
        return False
    if v in ("auto", ""):
        return True
    # a typo'd knob must not silently serve through a different decode
    # path than the user believes (the describe() banner principle)
    raise ValueError(f"{_ENV_FD_STREAM}={v!r} is not one of "
                     "auto/1/0/true/false/on/off")


def fd_stream_block() -> int:
    """Overlap-save block size C: the ring holds the last C tokens, block
    spectra are length-2C rffts, and the kernel-tail refresh runs every C
    steps. Larger C amortises the refresh further but grows the direct
    head work (O(C·d) per token) and the refresh latency spike."""
    c = _env_int(_ENV_FD_STREAM_C, 64)
    if c < 2:
        raise ValueError(f"{_ENV_FD_STREAM_C}={c} must be >= 2")
    return c


# ------------------------------------------------- large-rank SKI policy
#: dense (d, r, r) Gram budget for the original fused kernel (bytes)
SKI_GRAM_BYTES_MAX = 64 << 20


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError:
        # a typo'd knob must not silently dispatch to a different kernel
        # variant than the user believes (the describe() banner principle)
        raise ValueError(f"{name}={v!r} is not an integer") from None


def ski_dense_rank_max() -> int:
    """Largest r served by the dense-Gram fused kernel (the (bd, r, r)
    VMEM panel; paper's dense-beats-FFT observation holds to here)."""
    return _env_int(_ENV_DENSE_RMAX, 512)


def ski_windowed_rank_max() -> int:
    """Largest r served by the windowed banded-W kernel; beyond it the
    per-row O(r) band work loses to the O(log r) FFT-Gram amortisation."""
    return _env_int(_ENV_WINDOWED_RMAX, 4096)


def band_budget() -> int:
    """Max Gram band width bw: per-tile band-block VMEM is bd·bw²·4 B
    (plus the (bd, 2rp-1) coefficient line), so 128 keeps the transient
    block ≤ 0.5 MB at the interpret-default bd=8 and ≤ 8 MB at the
    compiled lane width bd=128."""
    return _env_int(_ENV_BAND_MAX, 128)


def ski_rank_variant(r: int, d: int | None = None) -> str:
    """How the fused SKI pipeline applies the r×r inducing Gram:
    "dense" | "windowed" | "fft" (see module docstring). ``d`` (channels)
    feeds the dense (d, r, r) byte budget when known."""
    if r <= ski_dense_rank_max() and (
            d is None or d * r * r * 4 <= SKI_GRAM_BYTES_MAX):
        return "dense"
    if r <= ski_windowed_rank_max():
        return "windowed"
    return "fft"


def band_width(bn: int, n: int, r: int) -> int:
    """Static Gram band width covering every hat tap of a length-bn
    sequence tile: the tile's rows span (bn-1)/h inducing columns, plus
    one tap each side and fp32-floor slack, rounded to the sublane unit
    and capped at the (padded) grid size."""
    h = (n - 1) / max(1, r - 1)
    bw = round_up(int((bn - 1) / h) + 4, 8)
    return max(8, min(bw, round_up(r, 8)))


def band_fit(bn: int, n: int, r: int) -> tuple[int, int]:
    """(bn, bw) with bn shrunk (halved to the sublane floor) until the
    band fits :func:`band_budget` — band width follows the sequence tile
    (bw ≈ bn·r/n), so shrinking the tile is the legal way to shrink the
    band without changing semantics."""
    bw = band_width(bn, n, r)
    while bw > band_budget() and bn > 8:
        bn = max(8, round_up(bn // 2, 8))
        bw = band_width(bn, n, r)
    return bn, bw


# ---------------------------------------------------------- shape fitting
def round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def lane_unit(interpret: bool) -> int:
    """Last-dim (lane) padding unit: 128 on compiled TPU, 8 elsewhere."""
    return 8 if interpret else 128


def fit_block(size: int, target: int, unit: int = 8) -> int:
    """Largest-balanced block <= target for a possibly-ragged dimension.

    Splits ``size`` into ceil(size/target) near-equal tiles rounded up to
    ``unit`` so padding waste stays < unit per tile (e.g. n=300, target=256
    -> bn=152, padded n=304 — not 512)."""
    if size <= target:
        return round_up(size, unit)
    tiles = -(-size // target)
    return round_up(-(-size // tiles), unit)


# --------------------------------------------------------- autotune cache
_DEFAULT_TARGETS = {
    # kernel -> (bn target, bd target) heuristic starting point
    "short_conv": (256, 128),
    "interp_reduce": (256, 128),
    "interp_expand": (256, 128),
    "ski_fused": (256, 128),
    "ski_windowed": (256, 128),
    "ski_expand2": (256, 128),
    "conv_tap_grad": (256, 128),
    # causal FD-TNO pipeline (kernels/fd_fused.py): freq-tile × d-tile for
    # the spectral multiply / khat reduction, d-tile × lag-tile for the
    # Hilbert lag window
    "fd_mul": (256, 128),
    "fd_khat_grad": (256, 128),
    "hilbert_window": (128, 512),
}

_cache_lock = threading.Lock()
_cache_data: dict | None = None
_pretuned_data: dict | None = None

#: shipped autotune tables (one file per platform×mode, e.g.
#: cpu_interpret.json) — measured once and committed so fresh checkouts
#: start from tuned blocks instead of the shape heuristic. Consulted only
#: when ``REPRO_AUTOTUNE_CACHE`` is unset; an explicit cache file is the
#: user saying "use exactly this table". Precedence:
#: user cache entry > pretuned entry > autotune sweep > heuristic.
PRETUNED_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "pretuned")


def cache_path() -> str:
    return os.environ.get(
        _ENV_CACHE,
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "autotune.json"))


def _load_pretuned() -> dict:
    global _pretuned_data
    if _pretuned_data is None:
        entries: dict = {}
        try:
            for fn in sorted(os.listdir(PRETUNED_DIR)):
                if not fn.endswith(".json"):
                    continue
                try:
                    with open(os.path.join(PRETUNED_DIR, fn)) as f:
                        data = json.load(f)
                except (OSError, ValueError):
                    continue
                if isinstance(data, dict):
                    entries.update(data.get("entries", {}))
        except OSError:
            pass
        _pretuned_data = entries
    return _pretuned_data


def _load_cache() -> dict:
    global _cache_data
    if _cache_data is None:
        try:
            with open(cache_path()) as f:
                data = json.load(f)
            _cache_data = data.get("entries", {}) if isinstance(data, dict) else {}
        except (OSError, ValueError):
            _cache_data = {}
    return _cache_data


def _save_cache() -> None:
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump({"version": 1, "entries": _cache_data}, f, indent=1,
                      sort_keys=True)
    except OSError:
        pass                      # read-only FS: tuning just isn't persisted


def clear_cache(memory_only: bool = False) -> None:
    """Drop the in-memory caches (tests); optionally keep the file. The
    pretuned table memo is reset too so env-var changes re-resolve."""
    global _cache_data, _pretuned_data
    with _cache_lock:
        _cache_data = None
        _pretuned_data = None
        if not memory_only:
            try:
                os.remove(cache_path())
            except OSError:
                pass


def _key(kernel: str, n: int, d: int, dtype, interpret: bool,
         extra: str = "") -> str:
    mode = "interpret" if interpret else "compiled"
    tail = f"|{extra}" if extra else ""
    return (f"{kernel}|n={n}|d={d}|{jax.numpy.dtype(dtype).name}"
            f"|{platform()}|{mode}{tail}")


def autotune_enabled() -> bool:
    return os.environ.get(_ENV_AUTOTUNE, "0").lower() in ("1", "true")


def is_concrete(*arrays) -> bool:
    """True when no argument is a tracer (so timing sweeps are possible)."""
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def heuristic_blocks(kernel: str, n: int, d: int, interpret: bool) -> tuple[int, int]:
    tn, td = _DEFAULT_TARGETS.get(kernel, (256, 128))
    return fit_block(n, tn, 8), fit_block(d, td, lane_unit(interpret))


def clamp_blocks(bn: int, bd: int, n: int, d: int,
                 interpret: bool) -> tuple[int, int]:
    """Shrink cached/requested blocks to the actual array, preserving the
    sublane (8) / lane (128 compiled, 8 interpret) padding units — shared
    by every kernel wrapper so the clamp policy lives in one place."""
    return (min(bn, round_up(n, 8)),
            min(bd, round_up(d, lane_unit(interpret))))


def _candidates(n: int, d: int, interpret: bool):
    ud = lane_unit(interpret)
    bns = sorted({fit_block(n, t, 8) for t in (128, 256, 512)})
    bds = sorted({fit_block(d, t, ud) for t in (128, 256)})
    return [(bn, bd) for bn in bns for bd in bds]


def _time_call(fn, iters: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def get_blocks(kernel: str, n: int, d: int, dtype, interpret: bool,
               tune_call=None, extra: str = "") -> tuple[int, int]:
    """(bn, bd) for a kernel instance:
    cache > pretuned table (env cache unset) > autotune sweep > heuristic.

    ``tune_call(bn, bd)`` must execute the kernel with those blocks and
    return its output; pass it only when the inputs are concrete. ``extra``
    carries further legality/footprint parameters into the cache key
    (e.g. filter width m for short_conv — bn >= m — and rank r for the
    Gram-carrying fused kernel). The sweep runs once per (kernel, shape,
    dtype, platform, mode, extra) and persists to :func:`cache_path`.
    """
    key = _key(kernel, n, d, dtype, interpret, extra)
    with _cache_lock:
        hit = _load_cache().get(key)
    source = "cache"
    if hit is None and os.environ.get(_ENV_CACHE) is None:
        # no explicit cache file: seed from the shipped pretuned tables
        hit = _load_pretuned().get(key)
        source = "pretuned"
    if hit:
        _count_dispatch(kernel, source)
        return int(hit["bn"]), int(hit["bd"])
    if tune_call is not None and autotune_enabled():
        best, best_t, errors = None, float("inf"), []
        for bn, bd in _candidates(n, d, interpret):
            try:
                t = _time_call(lambda: tune_call(bn, bd))
            except Exception as e:  # noqa: BLE001 — a candidate may not compile
                errors.append(f"({bn}, {bd}): {type(e).__name__}: {e}")
                continue
            if t < best_t:
                best, best_t = (bn, bd), t
        if best is None:
            # every candidate failed: the kernel does not run here, and the
            # heuristic would only hide that until the real call fails
            raise RuntimeError(f"autotune of {key}: every candidate "
                               "failed\n" + "\n".join(errors))
        with _cache_lock:
            _load_cache()[key] = {"bn": best[0], "bd": best[1],
                                  "seconds": best_t}
            _save_cache()
        _count_dispatch(kernel, "autotune")
        return best
    _count_dispatch(kernel, "heuristic")
    return heuristic_blocks(kernel, n, d, interpret)


def _count_dispatch(kernel: str, source: str) -> None:
    """Per-op block-resolution counter (ISSUE 9): how each kernel's
    (bn, bd) was decided — cache hit, shipped pretuned table, fresh
    autotune sweep, or the heuristic fallback. Routed through the lazy
    process default registry (a no-op unless ``REPRO_METRICS`` is set or
    an explicit registry was installed), so the resolve path — already
    trace-time only — costs one no-op call when observability is off."""
    from repro.obs import metrics as obs_metrics
    obs_metrics.default_registry().counter(
        "repro_kernel_dispatch_total",
        "kernel block resolutions by source",
        ("kernel", "source")).labels(kernel=kernel, source=source).inc()
