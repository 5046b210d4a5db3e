#!/usr/bin/env python3
"""Readings that the limits in ``bench/limits/<cell>.json`` are set from,
in one process on the chip.

    python3 bench/calibrate.py --workload train_fd_8k --seeds 1,2,3 \\
        --control-seeds 4,5,6 --fault half_batch --fault-seeds 7,8,9 \\
        --out chiprun_out/cal_train_fd_8k.json

* ``--seeds``: sound runs of the program (the lower readings), each of
  the cell's check steps only.
* ``--control-seeds``: sound runs that also read the control: the plain
  reference computed in bfloat16 (the precision below the configuration's
  float32) in the program's place, on the same batches.
* ``--fault`` with ``--fault-seeds``: runs with that fault planted in the
  program (bench/drivers/<kind>.py ``plant_fault``).

Every reading is printed as it comes and all of them are written to
``--out``. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import importlib

    from bench import common
    c = common.cell(args.workload)
    common.setup_env(args.rehearse)
    common.configure_jax(args.rehearse)
    import jax.numpy as jnp
    devs = common.devices(c["chips"], args.rehearse)
    driver = importlib.import_module(f"bench.drivers.{c['driver']}")
    runs = ([("sound", s, None, None) for s in _ints(args.seeds)]
            + [("control", s, None, jnp.bfloat16)
               for s in _ints(args.control_seeds)]
            + [(f"fault:{args.fault}", s, args.fault, None)
               for s in _ints(args.fault_seeds)])
    rows = []
    for kind, seed, fault, control in runs:
        t0 = time.perf_counter()
        out = driver.run(c, seed=seed, seconds=0.0, trace=False,
                         rehearse=args.rehearse, t_start=t0, devs=devs,
                         fault=fault, control=control, check_only=True)
        row = {"kind": kind, "seed": seed,
               "numbers": {k: v for k, v in out["numbers"].items()
                           if not k.startswith("_")},
               "where": out["numbers"].get("_where"),
               "seconds": time.perf_counter() - t0}
        if "control_numbers" in out:
            row["control"] = {k: v for k, v in out["control_numbers"].items()
                              if not k.startswith("_")}
            row["control_where"] = out["control_numbers"].get("_where")
        rows.append(row)
        common.say(json.dumps(row))
        del out
    res = {"workload": args.workload, "rows": rows,
           "device": common.device_record(devs)}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    print(json.dumps({"workload": args.workload, "runs": len(rows)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
