"""Federated rounds completed per second over the whole window (host
clock; the window ends after block_until_ready)."""


def read(run):
    return run.rounds / run.window_s
