"""Model FLOP/s utilisation of training: the operations the forward and
backward passes require per token (bench/flops.py: 6 x the matmul
weights plus the mixer's transforms; no recomputation) times the
window's tokens/s, over chips x peak FLOP/s, in %."""
from bench import flops


def read(run):
    w = run.window
    if not w.get("tokens_per_s"):
        return None
    per_tok = flops.train_flops_per_token(run.cfg, w["seq_len"])
    return 100.0 * per_tok * w["tokens_per_s"] / (run.chips
                                                  * run.peaks["flops"])
