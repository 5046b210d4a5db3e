"""Plain reference of the TNN causal language model (Qin et al., "Toeplitz
Neural Network for Sequence Modeling", ICLR 2023) with the causal
frequency-domain (FD) mixer of Moreno et al., "SKI to go Faster" (2023),
Algorithm 2, at the widths the configuration file states.

Straightforward jax.numpy, written from the published equations and from
the seeded initialisation the system under test documents; it imports
nothing of that system and takes nothing it made. No kernels, no cache,
no batching tricks: the Toeplitz action is an FFT convolution of the
causal kernel with the input, zero-padded to twice the length, so that
nothing wraps around.

Model (pre-norm residual, per layer):
    h = rmsnorm(x);  u = silu(h Wu);  v = silu(h Wv)
    x = x + (T u * v) Wo                       (GTU; T = causal Toeplitz)
    h = rmsnorm(x);  x = x + (silu(h Wg) * (h Wup)) Wdown
then rmsnorm and the LM head, cross-entropy over the real vocabulary.

Causal FD kernel of a layer over n positions, per channel: the RPE (an
MLP: Linear, then LayerNorm and ReLU between layers) gives the real part
R of the response on the rfft grid of a length-2n signal, w_m = m/n
(m = 0..n); k = irfft(R, 2n) times the analytic window (1 at lag 0, 2 at
lags 1..n-1). That is the kernel whose spectrum is R - i H{R} (Hilbert
completion), restricted to the causal lags.

``dtype`` runs the whole model in another precision (the control:
bfloat16 parameters, activations and matmul outputs; the FFTs take and
give bfloat16 values around a float32 transform, which has no bfloat16
form). Matmuls run at the precision the caller sets; the check sets
``highest``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


class _Keys:
    """Split the running key, hand out the second half (the seeded
    initialisation draws keys this way, one per parameter group)."""

    def __init__(self, key):
        self.key = key

    def __call__(self):
        self.key, sub = jax.random.split(self.key)
        return sub


def _lecun(key, shape):
    fan_in = math.prod(shape[:-1]) if len(shape) >= 2 else shape[0]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, F32)


def _linear(key, d_in, d_out, bias):
    k = _Keys(key)
    p = {"w": _lecun(k(), (d_in, d_out))}
    if bias:
        k()
        p["b"] = jnp.zeros((d_out,), F32)
    return p


def _rpe_init(key, cfg):
    k = _Keys(key)
    hid, n_l = cfg["rpe_hidden"], cfg["rpe_layers"]
    dims = [1] + [hid] * (n_l - 1) + [cfg["d_model"]]
    layers = []
    for i in range(n_l):
        lp = _linear(k(), dims[i], dims[i + 1], True)
        if i < n_l - 1:
            k()
            lp["ln"] = {"scale": jnp.ones((dims[i + 1],), F32),
                        "bias": jnp.zeros((dims[i + 1],), F32)}
        layers.append(lp)
    return {"rpe": {"layers": layers}}


def _layer_init(key, cfg):
    d, f = cfg["d_model"], cfg["d_ff"]
    k = _Keys(key)
    k()                                   # norm1 (ones)
    km = _Keys(k())
    mixer = {"wu": _linear(km(), d, d, False),
             "wv": _linear(km(), d, d, False),
             "wo": _linear(km(), d, d, False),
             "tno": _rpe_init(km(), cfg)}
    k()                                   # norm2 (ones)
    kf = _Keys(k())
    ffn = {"w_gate": _lecun(kf(), (d, f)), "w_up": _lecun(kf(), (d, f)),
           "w_down": _lecun(kf(), (f, d))}
    ones = jnp.ones((d,), F32)
    return {"norm1": {"scale": ones}, "mixer": mixer,
            "norm2": {"scale": ones}, "ffn": ffn}


def init_params(cfg: dict, seed: int) -> dict:
    """Parameters from the seed, in the tree the system's checkpoints
    use (layers stacked on a leading axis under blocks/sub0)."""
    k = _Keys(jax.random.PRNGKey(seed))
    v, d = cfg["vocab_padded"], cfg["d_model"]
    p = {"embed": 0.02 * jax.random.normal(k(), (v, d), F32),
         "unembed": _lecun(k(), (d, v))}
    k()                                   # the layer-shape template draw
    keys = jax.random.split(k(), cfg["n_layers"])
    layers = [_layer_init(_Keys(kk)(), cfg) for kk in keys]
    p["blocks"] = {"sub0": jax.tree.map(lambda *xs: jnp.stack(xs), *layers)}
    p["norm_f"] = {"scale": jnp.ones((d,), F32)}
    return p


# ------------------------------------------------------------------ forward
def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _layernorm(x, p, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rpe(p, pos):
    """pos (m,) -> (m, d)."""
    x = pos[:, None]
    layers = p["layers"]
    for i, lp in enumerate(layers):
        x = x @ lp["w"] + lp["b"]
        if i < len(layers) - 1:
            x = jax.nn.relu(_layernorm(x, lp["ln"]))
    return x


def causal_kernel(tno_p, cfg: dict, n: int):
    """(d, n) causal FD kernel, lags 0..n-1 (see the module docstring)."""
    if cfg["mixer"] != "fd":
        raise ValueError(f"no reference for mixer {cfg['mixer']!r}")
    dt = tno_p["rpe"]["layers"][0]["w"].dtype
    omega = (jnp.arange(n + 1, dtype=F32) / n).astype(dt)
    real = _rpe(tno_p["rpe"], omega).T                          # (d, n+1)
    kt = jnp.fft.irfft(real.astype(F32), n=2 * n, axis=-1)[:, :n]
    win = jnp.where(jnp.arange(n) == 0, 1.0, 2.0).astype(F32)
    return (kt * win).astype(dt)


def causal_conv(u, k):
    """y[b, t, c] = sum_{s <= t} k[c, t - s] u[b, s, c]; u (b, n, d)."""
    n = u.shape[1]
    uf = jnp.fft.rfft(u.astype(F32), n=2 * n, axis=1)
    kf = jnp.fft.rfft(k.astype(F32), n=2 * n, axis=-1).T[None]
    return jnp.fft.irfft(uf * kf, n=2 * n, axis=1)[:, :n].astype(u.dtype)


def _layer(lp, cfg, x):
    eps = cfg["norm_eps"]
    mp = lp["mixer"]
    h = _rmsnorm(x, lp["norm1"]["scale"], eps)
    u = jax.nn.silu(h @ mp["wu"]["w"])
    v = jax.nn.silu(h @ mp["wv"]["w"])
    k = causal_kernel(mp["tno"], cfg, x.shape[1])
    x = x + (causal_conv(u, k) * v) @ mp["wo"]["w"]
    h = _rmsnorm(x, lp["norm2"]["scale"], eps)
    fp = lp["ffn"]
    return x + (jax.nn.silu(h @ fp["w_gate"]) * (h @ fp["w_up"])) @ fp["w_down"]


def hidden(params, cfg: dict, tokens):
    """tokens (b, n) -> final-normed hidden states (b, n, d), layer by
    layer (each layer rematerialised in the backward)."""
    x = params["embed"][tokens]
    body = jax.checkpoint(functools.partial(_layer, cfg=cfg))
    for i in range(cfg["n_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["blocks"]["sub0"])
        x = body(lp, x=x)
    return _rmsnorm(x, params["norm_f"]["scale"], cfg["norm_eps"])


def logits_of(params, cfg: dict, h):
    z = h @ params["unembed"]
    return jnp.where(jnp.arange(z.shape[-1]) < cfg["vocab"], z,
                     jnp.asarray(-1e30, z.dtype))


def nll_sum(params, cfg: dict, tokens, labels, chunk: int = 2048):
    """Sum over positions of -log p(label), the logits made chunk by
    chunk of positions so that a long row fits."""
    h = hidden(params, cfg, tokens)
    n = h.shape[1]
    c = min(chunk, n)

    @jax.checkpoint
    def part(hc, lc):
        z = logits_of(params, cfg, hc)
        lse = jax.nn.logsumexp(z, axis=-1)
        ll = jnp.take_along_axis(z, lc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - ll).astype(F32))

    total = jnp.zeros((), F32)
    for s in range(0, n, c):
        total = total + part(h[:, s:s + c], labels[:, s:s + c])
    return total


# ---------------------------------------------------------------- training
def cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def lr_at(opt: dict, count: int) -> float:
    """Linear warm-up, then cosine decay to min_lr_frac of the rate."""
    warm = min(count / max(opt["warmup_steps"], 1), 1.0)
    t = (count - opt["warmup_steps"]) / max(
        opt["total_steps"] - opt["warmup_steps"], 1)
    t = min(max(t, 0.0), 1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * t))
    return opt["lr"] * warm * (opt["min_lr_frac"]
                               + (1.0 - opt["min_lr_frac"]) * cos)


def adamw(opt: dict, params, grads, mu, nu, count: int):
    """One AdamW step with global-norm clipping; count is 1-based.
    Returns (params, mu, nu, clipped grads)."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(F32)))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g.astype(F32) * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    lr = lr_at(opt, count)
    c1, c2 = 1.0 - b1 ** count, 1.0 - b2 ** count
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                                  + opt["weight_decay"] * p),
        params, mu, nu)
    return params, mu, nu, grads


def train_readings(cfg: dict, opt: dict, seed: int, batches, *,
                   dtype=F32) -> dict:
    """Run the first len(batches) training steps from the seeded
    initialisation: loss and gradient of the mean next-token loss over
    every row, a row at a time so that long rows fit, then AdamW.
    ``batches`` is a list of (tokens, labels) host arrays. Returns host
    values: losses, the clipped first gradient and the parameters before
    and after."""
    p0 = cast(init_params(cfg, seed), dtype)

    def row_loss(p, tok, lab):
        return nll_sum(p, cfg, tok, lab)

    vg = jax.jit(jax.value_and_grad(row_loss))
    params = p0
    mu = jax.tree.map(lambda a: jnp.zeros(a.shape, F32), p0)
    nu = jax.tree.map(lambda a: jnp.zeros(a.shape, F32), p0)
    losses, g_first = [], None
    for i, (tok, lab) in enumerate(batches):
        rows = tok.shape[0]
        total = jnp.zeros((), F32)
        grads = jax.tree.map(lambda a: jnp.zeros(a.shape, F32), params)
        for r in range(rows):
            s, g = vg(params, jnp.asarray(tok[r:r + 1]),
                      jnp.asarray(lab[r:r + 1]))
            total = total + s.astype(F32)
            grads = jax.tree.map(lambda a, b: a + b.astype(F32), grads, g)
        ntok = rows * tok.shape[1]
        losses.append(float(total / ntok))
        grads = jax.tree.map(lambda a: a / ntok, grads)
        p32 = cast(params, F32)
        p32, mu, nu, gc = adamw(opt, p32, grads, mu, nu, i + 1)
        params = cast(p32, dtype)
        if i == 0:
            g_first = gc
    return {"losses": losses,
            "grad_first": jax.device_get(g_first),
            "params_first": jax.device_get(cast(p0, F32)),
            "params_last": jax.device_get(cast(params, F32))}
