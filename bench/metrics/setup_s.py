"""Process start to the end of the last set-up step: imports, data and
weights, compilation (or loading it from the persistent cache), and the
first steps the check replays."""


def read(run):
    return run.setup_s
