"""The flat aggregation kernel's share of its roofline, in %: the least
time the chip could take for the window's aggregations (the larger of
FLOPs over peak FLOP/s and bytes over HBM bandwidth, from the unpadded
(K, D) shapes and the wire's width, bench/lib/flops.py) over the
kernel's summed device time in the trace.  One aggregation per round.
Read for any suffix (.paper, .lm)."""
from bench.lib import trace as tr
from bench.lib.flops import weighted_agg_cost


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    pats = run.cfg["kernels"]["weighted_agg"]
    secs = tr.op_seconds(run.trace, pats, run.trace_lo, run.trace_hi)
    if secs <= 0:
        return None
    flops, nbytes = weighted_agg_cost(run.K, run.D,
                                      run.cfg["training"]["wire_bytes"])
    least = max(flops / run.peaks["flops_bf16"],
                nbytes / run.peaks["hbm_bytes_per_s"]) * run.rounds
    # kernel time summed over chips; each chip reduces its own clients
    return 100.0 * least / secs
