"""The fused SSD scan's share of its roofline, in %: the least time the
chip could take for the window's SSD, forward and backward (the larger
of FLOPs over peak FLOP/s and bytes over HBM bandwidth, from the
configuration's shapes, bench/lib/hybrid_flops.py), over the summed
device time of the ops the configuration's ``kernels.ssd_scan`` names
start with (``ssd_scan_fwd``, also run as the remat forward, and
``ssd_scan_bwd``; recompute is time, not work).  The SSD's decay cumsum
outside the kernels is neither work nor time here.  Every client
computes every local step of a round, so the window does rounds x
clients x local steps of them.  Read for any suffix (.hybrid); None
where the configuration names no such kernel or none ran."""
from bench.lib.hybrid_flops import ssd_scan_cost

from bench.metrics.moe_gmm_roofline import least_seconds, op_seconds


def read(run):
    secs = op_seconds(run, "ssd_scan")
    if secs <= 0:
        return None
    return 100.0 * least_seconds(run, ssd_scan_cost) / secs
