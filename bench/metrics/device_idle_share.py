"""Share of the traced window in which no operation ran on the device
(100 x (1 - busy / window)); busy is the union of op intervals,
averaged over the chips.  Read for any suffix (.paper, .lm)."""
from bench.lib import trace as tr


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    window = run.trace_hi - run.trace_lo
    busy = tr.mean_busy_s(run.trace, run.trace_lo, run.trace_hi) * 1e9
    return 100.0 * (1.0 - busy / window)
