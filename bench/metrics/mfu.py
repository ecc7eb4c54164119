"""Model FLOP utilization of the whole round over the traced window:
FLOPs of the applied local steps (forward and backward, from the
configuration's shapes, bench/lib/flops.py) over window x chips x the
chip's bf16 peak, in %.  Masked steps, remat recompute and the
aggregation are not counted.  Read for any suffix (.paper, .lm)."""


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    flops = run.applied_steps * run.work["flops"]
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peaks["flops_bf16"])
