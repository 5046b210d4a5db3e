"""What every cell's run shares: finding the cell's files, placing the
compile cache, checking the device, percentiles, and the result line."""
from __future__ import annotations

import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
#: JAX's persistent compilation cache: a fixed directory inside the
#: checkout (the directory is part of the cache key)
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")
#: run-time files (the training corpus, profiler traces); gitignored
WORK_DIR = os.path.join(CHECKOUT, ".bench_work")


class BenchError(RuntimeError):
    """A run that cannot produce a result; the message says why."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))


def cell(name: str) -> dict:
    """The cell's entry of BENCHMARK.json with its configuration, traffic
    and limits files loaded."""
    spec = benchmark()
    found = [w for w in spec["workloads"] if w["name"] == name]
    if not found:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{[w['name'] for w in spec['workloads']]}")
    w = dict(found[0])
    cfgs = {c["name"]: c for c in spec["configs"]}
    w["config_file"] = cfgs[w["config"]]["file"]
    w["cfg"] = load_json(os.path.join(CHECKOUT, w["config_file"]))
    w["traffic_spec"] = load_json(
        os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    w["driver"] = w["traffic_spec"]["kind"]
    w["limits"] = load_json(os.path.join(BENCH, "limits", name + ".json"))
    # a metric without ``workloads`` counts for every cell
    w["end_to_end"] = [m for m in spec["end_to_end"]
                       if name in m.get("workloads", [name])]
    reported = {m["name"] for m in w["end_to_end"]}
    w["per_layer"] = [m for m in spec["per_layer"]
                      if (name in m["workloads"] if "workloads" in m
                          else m["moves"] in reported)]
    return w


def cell_names(driver: str | None = None) -> list:
    """The cells of BENCHMARK.json, those run by ``driver`` if given."""
    names = [w["name"] for w in benchmark()["workloads"]]
    return [n for n in names if driver is None or cell(n)["driver"] == driver]


def cell_parts(c: dict, rehearse: bool) -> tuple[dict, dict]:
    """(configuration, traffic) of a cell, each with its ``rehearse``
    overrides applied for a rehearsal."""
    cfg, t = dict(c["cfg"]), dict(c["traffic_spec"])
    if rehearse:
        cfg.update(cfg["rehearse"])
        t.update(t["rehearse"])
    return cfg, t


def cell_limits(c: dict, rehearse: bool) -> dict:
    """The cell's limits, with their ``rehearse`` overrides for a
    rehearsal (the CPU computes in float32 on both sides, so a tiny
    model's control departs less than at the cell's own size)."""
    lim = dict(c["limits"])
    over = lim.pop("rehearse", {})
    if rehearse:
        lim.update(over)
    return lim


def setup_env(rehearse: bool) -> None:
    """Environment the program reads when JAX starts: the cache
    directory, and for a rehearsal the CPU with interpreted kernels."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # libtpu would log under a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
        os.environ["REPRO_USE_PALLAS"] = "1"
        os.environ["REPRO_PALLAS_INTERPRET"] = "1"
    for p in (os.path.join(CHECKOUT, "src"), CHECKOUT):
        if p not in sys.path:
            sys.path.insert(0, p)


def configure_jax(rehearse: bool) -> None:
    import jax
    if rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
        return
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # every program in the cache after the first run, however quick its
    # compile was: set-up then differs from run to run only by loads
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def devices(chips: int, rehearse: bool):
    """The chips the cell asks for. No TPU, or too few, is an error: a
    run never falls back to the CPU. A rehearsal takes CPU devices."""
    import jax
    devs = jax.devices()
    if rehearse:
        if len(devs) < chips:
            raise BenchError(f"rehearsal needs {chips} CPU devices, found "
                             f"{len(devs)} (set XLA_FLAGS="
                             "--xla_force_host_platform_device_count)")
        return devs[:chips]
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform!r} "
                         f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def program_config(cfg: dict, rehearse: bool):
    """The program's configuration, held to the configuration file: a run
    in which the program departs from it is no run of this cell."""
    from repro.configs import get_config, reduce_for_smoke
    from repro.models.transformer import _tno_cfg
    arch = cfg["arch"]
    pc = get_config(arch)
    if rehearse:
        pc = reduce_for_smoke(pc)
    mixer = pc.layers_spec[0][0]
    have = {"n_layers": pc.n_layers, "d_model": pc.d_model,
            "gtu_expand": _tno_cfg(pc, mixer, True).expand,
            "d_ff": pc.d_ff, "vocab": pc.vocab,
            "vocab_padded": pc.vocab_padded,
            "mixer": mixer, "rpe_layers": pc.tno_rpe_layers,
            "rpe_hidden": pc.tno_rpe_hidden, "rpe_act": pc.tno_rpe_act,
            "lam": pc.tno_lam, "act": pc.act, "norm_eps": pc.norm_eps,
            "dtype": pc.dtype, "param_dtype": pc.param_dtype}
    bad = {k: (v, cfg[k]) for k, v in have.items() if cfg[k] != v}
    if bad:
        raise BenchError(f"program config {arch} departs from the "
                         f"configuration file: {bad}")
    return pc


def device_record(devs) -> dict:
    """Platform, kind, count and the peak memory of the fullest chip. On
    a TPU the runtime holds an executable's temporaries apart from its
    buffers, as reserved memory (``peak_bytes_reserved``), so the peak
    is the peak of the buffers in use plus the peak reserved: for a
    training step, the state and batch plus the step's working set."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        used = int(stats.get("peak_bytes_in_use", 0))
        held = int(stats.get("peak_bytes_reserved", 0))
        say(f"memory of {d}: peak in use {used}, peak reserved {held}, "
            f"limit {stats.get('bytes_limit')}")
        peak = max(peak, used + held)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def is_compile(event: str) -> bool:
    """A jax.monitoring duration event that means an executable was
    compiled or loaded from the persistent cache (tracing alone is not)."""
    return "backend_compile" in event or "compilation_cache" in event


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def program_seed(seed: int) -> int:
    """The program's PRNG keys and data seeds take 31 bits; --seed may be
    larger. The same --seed always gives the same program seed."""
    return int(seed) % (2 ** 31 - 1)


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
