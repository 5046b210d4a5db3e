"""Generic model assembly: decoder LMs, enc-dec, prefix-VLM, hybrid/SSM —
all driven by ArchConfig.pattern, with layers scanned over pattern periods
(small HLO, fast compile, remat-friendly) and the paper's TNO variants
available as drop-in token mixers.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.block import TNNBlockConfig, gtu_apply, gtu_init
from repro.core.tno import TNOConfig
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models.config import ArchConfig
from repro.models.context import Ctx, shard
from repro.nn.layers import ACTS, rmsnorm, rmsnorm_init
from repro.nn.params import KeyGen, boxed, rebox, unbox


# ------------------------------------------------------------------ pieces
def ffn_init(key, cfg: ArchConfig):
    kg = KeyGen(key)
    d, f = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "w_gate": boxed(kg(), (d, f), ("embed", "ffn"), "lecun", dt),
        "w_up": boxed(kg(), (d, f), ("embed", "ffn"), "lecun", dt),
        "w_down": boxed(kg(), (f, d), ("ffn", "embed"), "lecun", dt),
    }


def ffn_apply(params, cfg: ArchConfig, ctx: Ctx, x):
    act = ACTS[cfg.act]
    h = act(x @ params["w_gate"].astype(x.dtype)) * (x @ params["w_up"].astype(x.dtype))
    h = shard(ctx, h, "batch", "seq_any", "ffn")
    return h @ params["w_down"].astype(x.dtype)


def _tno_cfg(cfg: ArchConfig, variant: str, causal: bool,
             use_pallas: bool | None = None) -> TNNBlockConfig:
    tno = TNOConfig(
        d=cfg.d_model, variant=variant, causal=causal, lam=cfg.tno_lam,
        rpe_hidden=cfg.tno_rpe_hidden, rpe_layers=cfg.tno_rpe_layers,
        rpe_act=cfg.tno_rpe_act, rank=cfg.tno_rank,
        filter_size=cfg.tno_filter, use_pallas=use_pallas)
    return TNNBlockConfig(cfg.d_model, tno=tno, act=cfg.act)


# ------------------------------------------------------------------ layers
def mixer_init(key, cfg: ArchConfig, mixer: str, *, causal=True):
    if mixer in ("attention", "local"):
        return attn_init_wrap(key, cfg)
    if mixer == "mamba":
        return mb.mamba_init(key, cfg)
    if mixer in ("tno", "ski", "fd"):
        return gtu_init(key, _tno_cfg(cfg, mixer, causal))
    raise ValueError(mixer)


def attn_init_wrap(key, cfg):
    return attn.attn_init(key, cfg)


def mixer_apply(params, cfg: ArchConfig, ctx: Ctx, mixer: str, x, *,
                mask_kind, prefix=0):
    if mixer in ("attention", "local"):
        mk = "local" if mixer == "local" else mask_kind
        return attn.attn_apply(params, cfg, ctx, x, mask_kind=mk, prefix=prefix)
    if mixer == "mamba":
        return mb.mamba_apply(params, cfg, ctx, x)
    if mixer in ("tno", "ski", "fd"):
        causal = mask_kind in ("causal", "local")
        bcfg = _tno_cfg(cfg, mixer, causal, ctx.use_pallas)

        def gtu(p, h):
            # GTU internals run fp32 (FFTs); keep the residual dtype stable
            return gtu_apply(p, bcfg, h).astype(h.dtype)
        if mixer != "tno":                 # ski / fd dispatch to kernels
            gtu = _per_device_kernels(ctx, gtu)
        return gtu(params, x)
    raise ValueError(mixer)


def _per_device_kernels(ctx: Ctx, fn):
    """XLA cannot partition a Mosaic kernel: on a mesh of more than one
    device, run ``fn(params, x)`` per device under shard_map — batch split
    over the data axes, params replicated — when the Pallas path is on."""
    from repro.kernels import backend
    mesh = ctx.mesh
    if (mesh is None or mesh.size == 1
            or not backend.resolve_use_pallas(ctx.use_pallas)):
        return fn
    data = tuple(a for a in ctx.data_axes if a in mesh.shape) or None
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(), P(data)),
                         out_specs=P(data), check_vma=False)


def layer_init(key, cfg: ArchConfig, mixer: str, ffn: str, *, cross=False,
               causal=True):
    kg = KeyGen(key)
    p = {
        "norm1": rmsnorm_init(kg(), cfg.d_model),
        "mixer": mixer_init(kg(), cfg, mixer, causal=causal),
    }
    if cross:
        p["norm_x"] = rmsnorm_init(kg(), cfg.d_model)
        p["cross"] = attn.attn_init(kg(), cfg, cross=True)
    if ffn == "dense":
        p["norm2"] = rmsnorm_init(kg(), cfg.d_model)
        p["ffn"] = ffn_init(kg(), cfg)
    elif ffn == "moe":
        p["norm2"] = rmsnorm_init(kg(), cfg.d_model)
        p["ffn"] = moe_mod.moe_init(kg(), cfg)
    return p


def _gathered_norm(params_norm, cfg, ctx, x):
    """SP gather + norm, ordered so the collective moves bf16.

    rmsnorm is per-position, so norm∘gather == gather∘norm; gathering the
    bf16 residual FIRST halves the all-gather bytes vs letting XLA hoist
    the gather inside the norm's fp32 region (§Perf iteration 1: 2×
    f32(b,s,d) gathers were 28% of qwen train_4k collective bytes)."""
    xg = shard(ctx, x, "batch", "seq_any", "embed")     # bf16 gather
    return rmsnorm(params_norm, xg, cfg.norm_eps)


def layer_apply(params, cfg: ArchConfig, ctx: Ctx, mixer: str, ffn: str, x,
                *, mask_kind, prefix=0, enc_out=None):
    x = shard(ctx, x, "batch", "seq", "embed")
    h = _gathered_norm(params["norm1"], cfg, ctx, x)
    y = mixer_apply(params["mixer"], cfg, ctx, mixer, h,
                    mask_kind=mask_kind, prefix=prefix)
    # constrain the mixer/FFN output back to the seq-sharded layout BEFORE
    # the residual add: the partitioner then emits reduce-scatter on the
    # TP partial sums instead of full all-reduce + later re-shard
    x = x + shard(ctx, y, "batch", "seq", "embed")
    aux = jnp.zeros((), jnp.float32)
    if "cross" in params:
        h = _gathered_norm(params["norm_x"], cfg, ctx, x)
        y = attn.attn_apply(params["cross"], cfg, ctx, h,
                            mask_kind="full", kv_src=enc_out)
        x = x + shard(ctx, y, "batch", "seq", "embed")
    if ffn == "dense":
        h = _gathered_norm(params["norm2"], cfg, ctx, x)
        x = x + shard(ctx, ffn_apply(params["ffn"], cfg, ctx, h),
                      "batch", "seq", "embed")
    elif ffn == "moe":
        if cfg.moe_impl == "ep":
            # EP consumes seq-sharded tokens directly: no gather at all
            # (rmsnorm is per-position, so it commutes with the sharding)
            h = rmsnorm(params["norm2"], x, cfg.norm_eps)
        else:
            h = _gathered_norm(params["norm2"], cfg, ctx, x)
        y, aux = moe_mod.moe_apply(params["ffn"], cfg, ctx, h)
        x = x + shard(ctx, y, "batch", "seq", "embed")
    x = shard(ctx, x, "batch", "seq", "embed")
    return x, aux


# -------------------------------------------------------------- model init
def init_model(key, cfg: ArchConfig):
    """Returns a Box tree (call unbox() for (params, logical axes))."""
    kg = KeyGen(key)
    d = cfg.d_model
    dt = jnp.dtype(cfg.param_dtype)
    spec = cfg.layers_spec
    cross = cfg.kind == "encdec"
    causal = cfg.kind != "encoder"

    def init_block(k):
        kgb = KeyGen(k)
        return {f"sub{i}": layer_init(kgb(), cfg, m, f, cross=cross,
                                      causal=causal)
                for i, (m, f) in enumerate(spec[: cfg.period])}

    p: Dict[str, Any] = {}
    if cfg.vocab:
        p["embed"] = boxed(kg(), (cfg.vocab_padded, d), (None, "embed_tp"),
                           "embed", dt, scale=0.02)
        p["unembed"] = boxed(kg(), (d, cfg.vocab_padded), ("embed", "vocab"),
                             "lecun", dt)
    nb = cfg.n_scan_blocks
    if nb:
        _, axes = unbox(init_block(kg()))             # axes template
        keys = jax.random.split(kg(), nb)
        vals = jax.vmap(lambda k: unbox(init_block(k))[0])(keys)
        p["blocks"] = rebox(vals, axes, prepend=("layers",))
    for i in range(cfg.n_tail_layers):
        li = nb * cfg.period + i
        m, f = spec[li]
        p[f"tail{i}"] = layer_init(kg(), cfg, m, f, cross=cross, causal=causal)
    p["norm_f"] = rmsnorm_init(kg(), d)

    if cfg.kind == "encdec":
        def init_enc_layer(k):
            return layer_init(k, cfg, "attention", "dense", causal=False)
        keys = jax.random.split(kg(), cfg.enc_layers)
        _, eaxes = unbox(init_enc_layer(keys[0]))
        evals = jax.vmap(lambda k: unbox(init_enc_layer(k))[0])(keys)
        p["enc_blocks"] = rebox(evals, eaxes, prepend=("layers",))
        p["enc_norm_f"] = rmsnorm_init(kg(), d)
    return p


# ------------------------------------------------------------ forward pass
def _maybe_remat(fn, cfg: ArchConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


def _run_blocks(params, cfg: ArchConfig, ctx: Ctx, x, *, mask_kind, prefix=0,
                enc_out=None):
    spec = cfg.layers_spec

    def block_fn(x, block_params):
        # remat at LAYER granularity: block-level checkpointing keeps the
        # whole period's cotangents + recompute buffers live at once
        # (141 GiB/device at jamba train_4k, 8-layer period); per-layer
        # remat bounds the backward working set to one sublayer.
        aux = jnp.zeros((), jnp.float32)
        for i in range(cfg.period):
            m, f = spec[i]

            def layer_fn(x, p, m=m, f=f):
                return layer_apply(p, cfg, ctx, m, f, x,
                                   mask_kind=mask_kind, prefix=prefix,
                                   enc_out=enc_out)

            x, a = _maybe_remat(layer_fn, cfg)(x, block_params[f"sub{i}"])
            aux = aux + a
        return x, aux
    aux_total = jnp.zeros((), jnp.float32)
    if cfg.n_scan_blocks:
        def scan_body(carry, bp):
            x, aux = carry
            x, a = block_fn(x, bp)
            return (x, aux + a), None
        (x, aux_total), _ = jax.lax.scan(scan_body, (x, aux_total),
                                         params["blocks"])
    for i in range(cfg.n_tail_layers):
        li = cfg.n_scan_blocks * cfg.period + i
        m, f = spec[li]

        def tail_fn(x, p, m=m, f=f):
            return layer_apply(p, cfg, ctx, m, f, x, mask_kind=mask_kind,
                               prefix=prefix, enc_out=enc_out)

        # remat unrolled layers too: keeps memory flat and makes the
        # unrolled cost probes (launch/dryrun) faithful to the scanned body
        x, a = _maybe_remat(tail_fn, cfg)(x, params[f"tail{i}"])
        aux_total = aux_total + a
    return x, aux_total


def _run_encoder(params, cfg: ArchConfig, ctx: Ctx, x):
    def body(x, bp):
        x, _ = layer_apply(bp, cfg, ctx, "attention", "dense", x,
                           mask_kind="full")
        return x, None
    body = _maybe_remat(body, cfg)
    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return rmsnorm(params["enc_norm_f"], x, cfg.norm_eps)


def embed_tokens(params, cfg: ArchConfig, ctx: Ctx, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    return shard(ctx, x.astype(jnp.dtype(cfg.dtype)), "batch", "seq", "embed")


def unembed(params, cfg: ArchConfig, ctx: Ctx, x):
    logits = x @ params["unembed"].astype(x.dtype)
    return shard(ctx, logits, "batch", "seq_any", "vocab")


def backbone(params, cfg: ArchConfig, ctx: Ctx, batch):
    """batch: dict -> (hidden (b, s, d) post-final-norm, aux). For
    prefix_vlm the prefix positions are already stripped."""
    mask_kind = "causal"
    prefix = 0
    enc_out = None
    if cfg.kind == "prefix_vlm":
        patches = batch["patches"].astype(jnp.dtype(cfg.dtype))
        tok_x = embed_tokens(params, cfg, ctx, batch["tokens"])
        x = jnp.concatenate([patches, tok_x], axis=1)
        mask_kind, prefix = "prefix", cfg.n_prefix
    elif cfg.kind == "encdec":
        enc_out = _run_encoder(params, cfg, ctx,
                               batch["enc_embed"].astype(jnp.dtype(cfg.dtype)))
        x = embed_tokens(params, cfg, ctx, batch["tokens"])
    else:
        x = embed_tokens(params, cfg, ctx, batch["tokens"])
    x, aux = _run_blocks(params, cfg, ctx, x, mask_kind=mask_kind,
                         prefix=prefix, enc_out=enc_out)
    x = rmsnorm(params["norm_f"], x, cfg.norm_eps)
    if cfg.kind == "prefix_vlm":
        x = x[:, cfg.n_prefix:]
    return x, aux


def forward(params, cfg: ArchConfig, ctx: Ctx, batch):
    """batch: dict -> (logits (b, s, V_pad), aux)."""
    x, aux = backbone(params, cfg, ctx, batch)
    return unembed(params, cfg, ctx, x), aux


def _ce_terms(cfg: ArchConfig, logits, labels):
    """Sum of per-token (lse - ll). logits fp32 (b, c, V_pad); labels
    (b, c). The label gather is a fused masked-reduce: never a one-hot
    matmul, and shard-friendly along a `model`-sharded vocab axis."""
    v = cfg.vocab_padded
    pad_mask = jnp.arange(v) < cfg.vocab
    logits = jnp.where(pad_mask[None, None, :], logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    sel = jnp.arange(v)[None, None, :] == labels[..., None]
    ll = jnp.sum(jnp.where(sel, logits, 0.0), axis=-1)
    return jnp.sum(lse - ll)


def loss_fn(params, cfg: ArchConfig, ctx: Ctx, batch, *, aux_weight=0.01):
    """Cross-entropy with sequence-chunked logits: the full (b, s, V)
    logits tensor is never materialised — each chunk's logits reduce to a
    scalar and are rematerialised in backward (jax.checkpoint), bounding
    CE memory to (b, loss_chunk, V). At vocab 262k × seq 4k this is the
    difference between fitting HBM and not."""
    x, aux = backbone(params, cfg, ctx, batch)
    labels = batch["labels"]
    b, s, d = x.shape

    def chunk_nll(xc, lc):
        logits = unembed(params, cfg, ctx, xc).astype(jnp.float32)
        return _ce_terms(cfg, logits, lc)

    c = cfg.loss_chunk
    if c and s > c and s % c == 0:
        nc = s // c
        xs = jnp.moveaxis(x.reshape(b, nc, c, d), 1, 0)        # (nc, b, c, d)
        ls = jnp.moveaxis(labels.reshape(b, nc, c), 1, 0)
        chunk_fn = jax.checkpoint(chunk_nll)

        def body(acc, inp):
            xc, lc = inp
            return acc + chunk_fn(xc, lc), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls),
                                unroll=nc if cfg.unroll_inner else 1)
    else:
        total = chunk_nll(x, labels)
    nll = total / (b * s)
    return nll + aux_weight * aux, {"nll": nll, "aux": aux}
