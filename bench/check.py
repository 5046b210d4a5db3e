"""The comparisons that decide ``correct``: what the timed path produced
against the plain reference, each number beside its limit.

Training, over the first steps of the very step object the window then
drives:
* ``loss_gap``   — worst relative gap of a step's loss;
* ``grad_gap``   — worst leaf: the gap between the program's and the
  reference's norm of the first clipped gradient (the program's read off
  Adam's first moment after one step), over the larger of that leaf's
  reference norm and the median leaf's;
* ``grad_gap_median`` — the same gap at the median leaf: steady from
  seed to seed where the worst leaf is one small leaf's rounding noise;
* ``update_gap`` — the same for the parameters' change over the steps,
  leaving out leaves whose reference gradient is under a thousandth of
  the median leaf's (Adam moves those by round-off alone).
A layer-stacked leaf counts as one leaf per layer.
"""
from __future__ import annotations

import numpy as np

#: leaves whose reference gradient norm is under this share of the median
#: leaf's are left out of the update comparison
NEGLIGIBLE_GRAD = 1e-3


def leaf_name(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx",
                                                   getattr(p, "name", p)))))
    return "/".join(parts)


def split_leaves(tree, fn):
    """{leaf name: fn(array)} with a layer-stacked leaf (under
    ``blocks``) split into one entry per layer."""
    import jax
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = leaf_name(path)
        if name.startswith("blocks/"):
            for i in range(x.shape[0]):
                out[f"{name}#{i}"] = fn(x[i])
        else:
            out[name] = fn(x)
    return out


def host_norms(tree) -> dict:
    return split_leaves(tree, lambda a: float(np.linalg.norm(
        np.asarray(a, np.float64).ravel())))


def host_diff_norms(after, before) -> dict:
    import jax
    diff = jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                        - np.asarray(b, np.float64), after, before)
    return host_norms(diff)


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """{leaf: |prog - ref| over the larger of the leaf's reference norm
    and the median leaf's}, over the leaves ``keep`` keeps."""
    names = [k for k in ref if keep is None or keep[k]]
    if set(prog) != set(ref):
        missing = sorted(set(ref) ^ set(prog))[:4]
        raise ValueError(f"leaf sets differ, e.g. {missing}")
    med = float(np.median([ref[k] for k in names]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in names}


def worst_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    gaps = leaf_gaps(prog, ref, keep)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog: losses, grad_norms, update_norms (leaf dicts); ref: the
    reference's losses, grad_first, params_first, params_last."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"]))
    ref_g = host_norms(ref["grad_first"])
    grad, grad_at = worst_gap(prog["grad_norms"], ref_g)
    grad_med = float(np.median(list(leaf_gaps(prog["grad_norms"],
                                              ref_g).values())))
    med = float(np.median(list(ref_g.values())))
    keep = {k: v >= NEGLIGIBLE_GRAD * med for k, v in ref_g.items()}
    ref_u = host_diff_norms(ref["params_last"], ref["params_first"])
    upd, upd_at = worst_gap(prog["update_norms"], ref_u, keep)
    return {"loss_gap": loss, "grad_gap": grad, "grad_gap_median": grad_med,
            "update_gap": upd,
            "_where": {"grad_gap": grad_at, "update_gap": upd_at,
                       "left_out": sorted(k for k, v in keep.items()
                                          if not v)}}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number at or under its limit. Returns (correct, the
    {name: {"value", "limit"}} record printed with the result)."""
    rec = {k: {"value": float(numbers[k]), "limit": float(limits[k])}
           for k in limits}
    ok = all(np.isfinite(r["value"]) and r["value"] <= r["limit"]
             for r in rec.values())
    return bool(ok), rec
