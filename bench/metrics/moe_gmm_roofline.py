"""The held experts' grouped matmuls' share of their roofline, in %: the
least time the chip could take for the window's grouped matmuls (the
larger of FLOPs over peak FLOP/s and bytes over HBM bandwidth, forward
and backward, from the configuration's shapes at the expected rows,
bench/lib/hybrid_flops.py) over the summed device time of the ops the
configuration's ``kernels.moe_gmm`` names start with (forward ``gmm``,
backward ``gmm`` and ``tgmm``; remat recompute is time, not work).

The expected rows, tokens x top_k x held / router_experts, stand for the
routed ones: at these widths the count is set by the held experts'
weight bytes, which routing imbalance does not change.  Every client
computes every local step of a round (masked or not), so the window does
rounds x clients x local steps of them.  Read for any suffix (.hybrid);
None where the configuration names no such kernel or none ran."""
from bench.lib import trace as tr
from bench.lib.hybrid_flops import moe_gmm_cost


def op_seconds(run, key):
    """Summed device seconds, over the chips, of the ops whose name
    starts with one of the configuration's ``kernels[key]`` patterns."""
    pats = tuple(run.cfg.get("kernels", {}).get(key, ()))
    if run.trace is None or run.peaks is None or not pats:
        return 0.0
    total = 0.0
    for ops in run.trace.devices.values():
        for o in ops:
            if run.trace_lo <= o.start_ns <= run.trace_hi \
                    and tr.short(o.name).startswith(pats):
                total += o.dur_ns
    return total / 1e9


def least_seconds(run, cost) -> float:
    t = run.cfg["training"]
    flops, nbytes = cost(run.cfg, t["batch"] * t["seq"])
    steps = run.rounds * run.K * t["local_steps"]
    return max(flops / run.peaks["flops_bf16"],
               nbytes / run.peaks["hbm_bytes_per_s"]) * steps


def read(run):
    secs = op_seconds(run, "moe_gmm")
    if secs <= 0:
        return None
    return 100.0 * least_seconds(run, moe_gmm_cost) / secs
