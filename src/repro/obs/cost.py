"""Analytic per-kernel cost model: FLOP/byte estimators + roofline math.

ISSUE 10 tentpole §1 — ``benchmarks/roofline.py``'s three-term analysis
lifted into a library the observability tier can consult at runtime.
Estimators are keyed off the SAME plan objects the kernel backend
dispatches on (:func:`repro.core.ski.ski_plan` /
:func:`repro.core.tno.tno_plan`), so "what should this op cost" and
"which kernel actually ran" cannot drift apart:

* :func:`cost_of_plan` — dispatch on a ski/tno plan dict → per-kernel
  :class:`Cost` map (the kernel names match
  ``backend._DEFAULT_TARGETS`` / ``repro_kernel_dispatch_total``
  labels wherever a Pallas kernel exists).
* family estimators — ``short_conv_cost``, ``interp_cost``,
  ``gram_cost`` (dense/windowed/fft), ``fd_mul_cost``,
  ``fd_khat_grad_cost``, ``hilbert_window_cost``, ``rfft_cost``,
  ``ssd_cost``, ``attention_decode_cost``.
* :func:`decode_step_cost` — a whole engine decode step (embed + every
  layer's mixer + FFN + LM head) as a per-family map; this is what
  :func:`repro.obs.devstats.attribute_engine` uses to split measured
  engine seconds across kernel families.
* roofline: :func:`seconds` (compute/memory terms under a platform
  :class:`Peaks`), :func:`achieved_fraction` (roofline-implied time /
  measured time), :func:`xla_cost` (the
  ``jit(...).lower().compile().cost_analysis()`` cross-check the unit
  tests pin the estimators against).

Estimates are *models*, not measurements: they count the algorithmic
multiply-adds and the unavoidable HBM traffic of each family. The
cross-check test keeps them within a small factor of XLA's own
cost_analysis on concrete shapes; the roofline fractions they imply are
for ranking kernels and spotting order-of-magnitude waste, not for
benchmarking.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Dict, Optional

_ENV_CPU_FLOPS = "REPRO_CPU_PEAK_FLOPS"
_ENV_CPU_BW = "REPRO_CPU_PEAK_BW"


@dataclasses.dataclass(frozen=True)
class Cost:
    """Algorithmic work of one kernel launch: floating-point operations
    and bytes moved to/from main memory (inputs + outputs, once each)."""
    flops: float
    bytes: float

    def __add__(self, other: "Cost") -> "Cost":
        return Cost(self.flops + other.flops, self.bytes + other.bytes)

    def scale(self, k: float) -> "Cost":
        return Cost(self.flops * k, self.bytes * k)


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Per-device roofline ceilings (FLOP/s, memory B/s, interconnect
    B/s). ``collective_bw=0`` means no interconnect term."""
    flops: float
    mem_bw: float
    collective_bw: float = 0.0


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError:
        raise ValueError(f"{name}={v!r} is not a number") from None


#: Per-chip peaks keyed by ``jax.Device.device_kind``, each with its
#: source. A device kind not in the table is an error, never a default.
PEAKS: Dict[str, Peaks] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    # HBM; 1,600 Gbit/s chip-to-chip interconnect, here as 50 GB/s a link
    "TPU v5 lite": Peaks(197e12, 819e9, 50e9),
}
#: CPU rehearsal entry (not a device metric): a conservative laptop-class
#: estimate, overridable via REPRO_CPU_PEAK_FLOPS / REPRO_CPU_PEAK_BW, so
#: CPU fractions rank kernels and are never MFU claims
CPU_KIND = "cpu"


def peaks(device_kind: Optional[str] = None) -> Peaks:
    """Roofline ceilings for a ``device_kind`` (default: the first
    device's). Raises for a kind not in :data:`PEAKS`."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    if device_kind == CPU_KIND:
        return Peaks(_env_float(_ENV_CPU_FLOPS, 5e10),
                     _env_float(_ENV_CPU_BW, 2e10), 0.0)
    if device_kind not in PEAKS:
        raise KeyError(f"no roofline peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)} (add it with its source)")
    return PEAKS[device_kind]


def dtype_bytes(dtype) -> int:
    import jax.numpy as jnp
    return jnp.dtype(dtype).itemsize


def fft_flops(n: int) -> float:
    """Real-input FFT of length n: ~2.5·n·log2(n) (split-radix real
    transform; the standard roofline convention)."""
    return 2.5 * n * math.log2(max(n, 2))


# -------------------------------------------------- per-family estimators
def short_conv_cost(n: int, m: int, d: int, batch: int = 1,
                    elem: int = 4) -> Cost:
    """Depthwise m-tap conv over (b, n, d): one multiply-add per tap."""
    return Cost(2.0 * batch * n * m * d,
                elem * (2.0 * batch * n * d + d * m))


def interp_cost(n: int, r: int, d: int, batch: int = 1,
                elem: int = 4) -> Cost:
    """One hat-interpolation pass (reduce z=Wᵀx or expand y=Wz): two
    taps per position, multiply-add each."""
    return Cost(4.0 * batch * n * d,
                elem * (batch * n * d + batch * r * d) + 8.0 * n)


def gram_cost(variant: str, r: int, d: int, batch: int = 1,
              elem: int = 4, bw: Optional[int] = None) -> Cost:
    """Applying the r×r inducing Gram per channel: dense matvec,
    banded (width bw) matvec, or circulant FFT matvec (length 2r)."""
    if variant == "dense":
        return Cost(2.0 * batch * d * r * r,
                    elem * (d * r * r + 2.0 * batch * r * d))
    if variant == "windowed":
        if bw is None:
            from repro.kernels import backend
            bw = min(backend.band_budget(), r)
        return Cost(2.0 * batch * d * r * bw,
                    elem * (d * (2 * r - 1) + 2.0 * batch * r * d))
    if variant == "fft":
        n2 = 2 * r
        per_ch = 2 * fft_flops(n2) + 6.0 * n2     # fwd+inv FFT + pointwise
        return Cost(batch * d * per_ch,
                    elem * (d * (2 * r - 1) + 2.0 * batch * r * d))
    raise ValueError(f"unknown gram variant {variant!r} "
                     "(want dense|windowed|fft)")


def rfft_cost(n: int, d: int, batch: int = 1, elem: int = 4) -> Cost:
    """One real FFT (or inverse) of length n per (batch, channel)."""
    return Cost(batch * d * fft_flops(n),
                elem * 2.0 * batch * n * d)


def fd_mul_cost(n_f: int, d: int, batch: int = 1, elem: int = 4) -> Cost:
    """Pointwise complex spectral multiply over n_f frequency bins:
    6 real flops per complex multiply."""
    return Cost(6.0 * batch * n_f * d,
                elem * (4.0 * batch * n_f * d + 2.0 * n_f * d))


def fd_khat_grad_cost(n_f: int, d: int, batch: int = 1,
                      elem: int = 4) -> Cost:
    """Backward khat reduction: conjugated multiply + batch-sum."""
    return Cost(8.0 * batch * n_f * d,
                elem * (4.0 * batch * n_f * d + 2.0 * n_f * d))


def hilbert_window_cost(n: int, d: int, elem: int = 4) -> Cost:
    """Causal (analytic-signal) lag window over the (d, n) response."""
    return Cost(4.0 * d * n, elem * 2.0 * d * n)


def ssd_cost(n: int, d_inner: int, state: int, batch: int = 1,
             elem: int = 4) -> Cost:
    """Selective state-space scan: per token, a (d_inner × state) update
    and readout (~6 flops per element)."""
    return Cost(6.0 * batch * n * d_inner * state,
                elem * (2.0 * batch * n * d_inner
                        + batch * d_inner * state))


def attention_decode_cost(n_ctx: int, heads: int, head_dim: int,
                          batch: int = 1, elem: int = 4) -> Cost:
    """One decode step against an n_ctx KV cache: QK^T + AV."""
    return Cost(4.0 * batch * heads * n_ctx * head_dim,
                elem * 2.0 * batch * n_ctx * heads * head_dim)


def mlp_cost(d_model: int, d_ff: int, batch: int = 1, tokens: int = 1,
             elem: int = 4) -> Cost:
    """Gated FFN: up + gate + down projections per token."""
    t = batch * tokens
    return Cost(2.0 * t * d_model * d_ff * 3,
                elem * (3.0 * d_model * d_ff + 2.0 * t * d_model))


def lm_head_cost(d_model: int, vocab: int, batch: int = 1,
                 elem: int = 4) -> Cost:
    return Cost(2.0 * batch * d_model * vocab,
                elem * (d_model * vocab + batch * (d_model + vocab)))


# -------------------------------------------------------- plan dispatch
def ski_plan_cost(plan: dict, n: int, d: int, batch: int = 1,
                  elem: int = 4, m: int = 4) -> Dict[str, Cost]:
    """Per-kernel cost of one fused SKI-TNO forward under ``plan``
    (:func:`repro.core.ski.ski_plan`): pass-1 reduce, the Gram apply in
    the plan's variant, pass-2 expand, and the m-tap sparse correction.
    Kernel keys match the backend dispatch names: the dense variant's
    Gram+expand+conv run as one ``ski_fused`` launch; windowed/fft split
    into ``ski_windowed``/``ski_fft_gram`` + the Gram-free
    ``ski_expand2``."""
    r = int(plan["r"])
    variant = plan.get("variant", "dense" if "a_dense" in plan
                       else "unfused")
    reduce_c = interp_cost(n, r, d, batch, elem)
    expand_c = interp_cost(n, r, d, batch, elem)
    conv_c = short_conv_cost(n, m, d, batch, elem)
    if variant in ("dense", "unfused"):
        return {"interp_reduce": reduce_c,
                "ski_fused": gram_cost("dense", r, d, batch, elem)
                + expand_c + conv_c}
    if variant == "windowed":
        return {"interp_reduce": reduce_c,
                "ski_windowed": gram_cost("windowed", r, d, batch, elem),
                "ski_expand2": expand_c + conv_c}
    if variant == "fft":
        return {"interp_reduce": reduce_c,
                "ski_fft_gram": gram_cost("fft", r, d, batch, elem),
                "ski_expand2": expand_c + conv_c}
    raise ValueError(f"ski plan with unknown variant {variant!r}")


def fd_plan_cost(plan: dict, n: int, d: int, batch: int = 1,
                 elem: int = 4) -> Dict[str, Cost]:
    """Per-kernel cost of one causal/acausal FD-TNO forward under a
    :func:`repro.core.tno.tno_plan` fd plan: x rfft + spectral multiply
    + irfft, plus (causal plans, ``khat_real``) the Hilbert completion
    of the real response."""
    n_f = n + 1                       # rfft bins of the length-2n embed
    out = {"rfft": rfft_cost(2 * n, d, batch, elem).scale(2.0),
           "fd_mul": fd_mul_cost(n_f, d, batch, elem)}
    if "khat_real" in plan:
        out["hilbert_window"] = hilbert_window_cost(n, d, elem)
    return out


def cost_of_plan(plan: dict, *, n: int, d: int, batch: int = 1,
                 dtype=None, m: int = 4) -> Dict[str, Cost]:
    """Dispatch on the SAME plan objects the kernel layer receives:

    * ski plan (``{"variant", "r", ...}``) → :func:`ski_plan_cost`;
    * fd plan (``{"khat"}`` / ``{"khat_real"}``) → :func:`fd_plan_cost`;
    * baseline tno plan (``{"coef"}``) → circulant Toeplitz matvec.
    """
    elem = 4 if dtype is None else dtype_bytes(dtype)
    if "variant" in plan or "a_dense" in plan:
        return ski_plan_cost(plan, n, d, batch, elem, m)
    if "khat" in plan or "khat_real" in plan:
        return fd_plan_cost(plan, n, d, batch, elem)
    if "coef" in plan:
        # dense Toeplitz matvec via length-2n circular embedding
        return {"toeplitz_fft": rfft_cost(2 * n, d, batch, elem).scale(3.0)
                + fd_mul_cost(n + 1, d, batch, elem)}
    raise ValueError(
        f"unrecognised plan keys {sorted(plan)}: want a ski plan "
        "(variant/a_dense), an fd plan (khat/khat_real), or a baseline "
        "plan (coef)")


def decode_step_cost(cfg, batch: int, max_len: int,
                     dtype=None) -> Dict[str, Cost]:
    """One engine decode step (S=batch slots, one token each) against a
    ``max_len`` cache, split per kernel family — the analytic share map
    :func:`repro.obs.devstats.attribute_engine` projects measured engine
    seconds onto. Mixer families follow ``cfg.layers_spec`` (the same
    per-layer table the model builds from)."""
    elem = 4 if dtype is None else dtype_bytes(dtype)
    d = cfg.d_model
    out: Dict[str, Cost] = {}

    def add(key: str, c: Cost):
        out[key] = out.get(key, Cost(0.0, 0.0)) + c

    add("embed", Cost(0.0, elem * float(batch * d)))
    c_blk = None
    for mixer, _ffn in cfg.layers_spec:
        if mixer == "fd":
            # streaming decode: O(C·d) ring head per token, spectra
            # refresh amortised over C steps (one block rfft + multiply)
            if c_blk is None:
                from repro.kernels import backend
                c_blk = backend.fd_stream_block()
            head = short_conv_cost(1, c_blk, d, batch, elem)
            refresh = (rfft_cost(2 * c_blk, d, batch, elem)
                       + fd_mul_cost(c_blk + 1, d, batch, elem)
                       ).scale(1.0 / c_blk)
            add("fd_stream", head + refresh)
        elif mixer in ("tno", "ski"):
            # hist-replay decode: the full Toeplitz row against max_len
            add("tno_hist", Cost(2.0 * batch * max_len * d,
                                 elem * batch * max_len * d))
        elif mixer in ("attention", "local"):
            heads = max(getattr(cfg, "n_heads", 1), 1)
            hd = max(d // heads, 1)
            n_ctx = (min(max_len, cfg.window) if mixer == "local"
                     and cfg.window else max_len)
            add("attention", attention_decode_cost(
                n_ctx, heads, hd, batch, elem))
        elif mixer == "mamba":
            add("ssd", ssd_cost(1, cfg.d_inner,
                                getattr(cfg, "ssm_state", 16), batch, elem))
        else:
            add(mixer or "mixer", Cost(2.0 * batch * d, elem * batch * d))
        add("mixer_proj", Cost(2.0 * batch * d * d * 2,
                               elem * 2.0 * d * d))
        add("mlp", mlp_cost(d, cfg.d_ff, batch, 1, elem))
    add("lm_head", lm_head_cost(d, cfg.vocab_padded, batch, elem))
    return out


def total(costs: Dict[str, Cost]) -> Cost:
    t = Cost(0.0, 0.0)
    for c in costs.values():
        t = t + c
    return t


# ------------------------------------------------------------- roofline
def seconds(cost: Cost, pk: Optional[Peaks] = None) -> dict:
    """Roofline-implied times for one launch: compute and memory terms,
    the binding one, and its name."""
    pk = pk or peaks()
    t_comp = cost.flops / max(pk.flops, 1.0)
    t_mem = cost.bytes / max(pk.mem_bw, 1.0)
    t_star = max(t_comp, t_mem)
    return {"compute_s": t_comp, "memory_s": t_mem, "bound_s": t_star,
            "dominant": "compute" if t_comp >= t_mem else "memory"}


def achieved_fraction(cost: Cost, measured_s: float,
                      pk: Optional[Peaks] = None) -> float:
    """Fraction of the roofline bound achieved: (time the dominant
    roofline term implies) / (measured time). 1.0 = at the roof; small
    values mean the kernel leaves the machine idle (launch overhead,
    bad tiling, interpreter overhead on CPU)."""
    if measured_s <= 0:
        return float("nan")
    return seconds(cost, pk)["bound_s"] / measured_s


# ------------------------------------------------- XLA cost cross-check
def xla_cost(fn, *args, **kwargs) -> Optional[dict]:
    """``jit(fn).lower(*args).compile().cost_analysis()`` reduced to
    ``{"flops": f, "bytes": b}``. Returns None when the backend does not
    expose cost analysis (some CPU wheels) — callers/tests must skip,
    not fail. This is the estimator's ground truth on shapes small
    enough to compile in a test."""
    import jax
    try:
        compiled = jax.jit(fn).lower(*args, **kwargs).compile()
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — availability probe, not a code path
        return None
    if ca is None:
        return None
    if isinstance(ca, (list, tuple)):      # older jax: one dict per device
        ca = ca[0] if ca else None
        if ca is None:
            return None
    flops = float(ca.get("flops", 0.0))
    nbytes = sum(float(v) for k, v in ca.items()
                 if "bytes accessed" in k and isinstance(v, (int, float)))
    return {"flops": flops, "bytes": nbytes, "raw": dict(ca)}


__all__ = [
    "Cost", "Peaks", "PEAKS", "peaks", "dtype_bytes", "fft_flops",
    "short_conv_cost", "interp_cost", "gram_cost", "rfft_cost",
    "fd_mul_cost", "fd_khat_grad_cost", "hilbert_window_cost",
    "ssd_cost", "attention_decode_cost", "mlp_cost", "lm_head_cost",
    "ski_plan_cost", "fd_plan_cost", "cost_of_plan", "decode_step_cost",
    "total", "seconds", "achieved_fraction", "xla_cost",
]
