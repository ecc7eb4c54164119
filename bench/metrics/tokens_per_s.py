"""Client tokens trained per second over the whole window: the sum over
rounds and clients of applied local steps s, times batch x sequence,
over the window's host-clock time.  Masked and inactive steps are not
useful work and do not count."""


def read(run):
    if not run.work["tokens"]:
        return None
    return run.applied_steps * run.work["tokens"] / run.window_s
