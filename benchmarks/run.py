"""Paper-quality reproductions — one module per paper table or figure.
CSV to stdout: ``name,value,unit,derived-claim``.

  bench_complexity           §3.2.1/§3.3: compiled HLO FLOPs of each TNO
                             variant against sequence length
  bench_pretrain_parity      Table 1 stand-in (causal quality parity)
  bench_lra_style            Table 2 stand-in (long-range classification)
  bench_length_extrapolation Fig 7a + §3.2.2 (inverse warp / FD grids)
  bench_decay_classes        Appendix E.3 (smoothness => decay, quantified)

They report model quality or compiled FLOPs, never wall time. Speed is
measured on the chip by the benchmark that ``BENCHMARK.json`` declares
(``bench/``); PERF.md says what it reads.

    PYTHONPATH=src:. python benchmarks/run.py
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    argparse.ArgumentParser(description=__doc__).parse_args()

    from benchmarks import (bench_complexity, bench_decay_classes,
                            bench_length_extrapolation, bench_lra_style,
                            bench_pretrain_parity)
    modules = [
        ("complexity", bench_complexity),
        ("pretrain_parity", bench_pretrain_parity),
        ("lra_style", bench_lra_style),
        ("length_extrapolation", bench_length_extrapolation),
        ("decay_classes", bench_decay_classes),
    ]
    print("name,value,unit,derived")
    for name, mod in modules:
        try:
            mod.run()
        except Exception as e:              # report, keep the harness alive
            print(f"{name}/ERROR,0,,{e!r}", file=sys.stderr)
            print(f"{name}/ERROR,0,error,{type(e).__name__}")


if __name__ == "__main__":
    main()
