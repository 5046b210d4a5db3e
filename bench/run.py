#!/usr/bin/env python3
"""Run one benchmark cell once and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python bench/run.py --workload <cell> --seed <n> --seconds <s> --rehearse

The cell is an entry of ``workloads`` in BENCHMARK.json; its
configuration, traffic mix and limits are files under ``bench/`` found by
name, and the traffic file names the driver (``bench/drivers/``) that
runs it. ``--trace 0`` reports the cell's end-to-end metrics; ``--trace
1`` its per-layer metrics, each read by ``bench/metrics/<name>.py`` from
the run's host records and a profiler trace of a short sub-window. Every
run checks what its timed path produced against the plain reference
(``bench/references/``) and prints each compared number beside its limit
as the last lines of standard error and under ``check`` in the result.

The last line of standard output is the result, one JSON object. A run
that finds no TPU, or fewer chips than the cell asks for, exits non-zero
and prints no result. ``--rehearse`` runs the cell at the configuration's
tiny widths on the CPU with interpreted kernels, to exercise the harness
end to end; it prints no result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU; prints no result line")
    return ap.parse_args(argv)


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def read_metric(name: str, run) -> float | None:
    """``bench/metrics/<name>.py``'s ``read(run)``; None = nothing to
    read in this run."""
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def result_line(c: dict, out: dict, trace: bool, checks: dict,
                correct: bool) -> dict:
    from bench import peaks
    dev = dict(out["device"])
    run = types.SimpleNamespace(
        cfg=out["cfg"], window=out.get("window", {}), trace=out["trace"],
        hlo=out.get("hlo"), chips=dev["count"], peaks=peaks.peaks(dev["kind"]))
    metrics = {}
    if not trace:
        for m in c["end_to_end"]:
            metrics[m["name"]] = {"value": _finite(out["e2e"][m["name"]]),
                                  "unit": m["unit"]}
    else:
        for m in c["per_layer"]:
            v = read_metric(m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if out["trace"] is not None:
            dev["busy_s"] = out["trace"].busy_s()
            dev["window_s"] = out["trace"].window_s
    res = {"correct": correct, "attempted": out["attempted"],
           "failed": out["failed"], "metrics": metrics, "device": dev}
    if trace and out.get("breakdown"):
        res["breakdown"] = out["breakdown"]
    res["check"] = checks
    return res


def main(argv=None) -> int:
    args = parse_args(argv)
    from bench import check, common
    if not os.path.isdir(os.path.join(common.CHECKOUT, "src", "repro")):
        common.say("no system under test: src/repro is missing from this "
                   "checkout")
        return 2
    try:
        c = common.cell(args.workload)
        common.setup_env(args.rehearse)
        common.configure_jax(args.rehearse)
        devs = common.devices(c["chips"], args.rehearse)
        driver = importlib.import_module(f"bench.drivers.{c['driver']}")
        out = driver.run(c, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), rehearse=args.rehearse,
                         t_start=T_START, devs=devs)
    except common.BenchError as e:
        common.say(f"FAILED: {e}")
        return 1
    correct, checks = check.judge(out["numbers"],
                                  common.cell_limits(c, args.rehearse))
    if args.rehearse:
        common.say(f"rehearsal of {args.workload}: e2e "
                   f"{ {k: v for k, v in out.get('e2e', {}).items()} }, "
                   f"attempted {out['attempted']} failed {out['failed']}")
    else:
        res = result_line(c, out, bool(args.trace), checks, correct)
    common.say(f"check detail: {out['numbers'].get('_where')}")
    for k, r in checks.items():
        print(f"check {k} {r['value']:.6g} limit {r['limit']:.6g}",
              file=sys.stderr, flush=True)
    if not args.rehearse:
        print(json.dumps(res, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
