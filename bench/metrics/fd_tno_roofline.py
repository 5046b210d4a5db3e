"""Roofline share of the causal FD-TNO op in training: the least time
its work needs (bench/flops.py fd_tno_op_cost, forward and backward, for
one chip's rows, once per layer and traced step) over the device time of
the ops under the ``repro_kernel.fd_tno`` scope in the trace, in %. The
ops in the scope are read from the step's optimised HLO (bench/trace.py
scope_ops). It counts the op's work, not one kernel's, so a change that
fuses or replaces its kernels reads against the same count."""
from bench import flops, trace

SCOPE = "repro_kernel.fd_tno"


def read(run):
    tr, w = run.trace, run.window
    if (tr is None or not run.hlo or run.cfg["mixer"] != "fd"
            or not w.get("traced_steps")):
        return None
    secs = tr.scoped_s(trace.scope_ops(run.hlo, SCOPE),
                       trace.module_name(run.hlo))
    if secs <= 0:
        return None
    ops, nbytes = flops.fd_tno_op_cost(run.cfg, w["batch"] // run.chips,
                                       w["seq_len"])
    least = max(ops / run.peaks["flops"], nbytes / run.peaks["hbm_bytes_per_s"])
    calls = run.cfg["n_layers"] * w["traced_steps"]
    return 100.0 * least * calls / secs
