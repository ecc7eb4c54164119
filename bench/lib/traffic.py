"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and, with the seed, gives what the federation
receives.

A mix names its ``kind``.  Each kind has a driver of its own,
``bench/drivers/<kind>.py``, found by that name: it builds the program
over the federation, makes one call of the window as users make it, and
reports what its calls did.  A new mix of a known kind is a data file
alone; a new kind is a new driver file, and no existing file changes.

Every kind takes the participation stream (which clients complete how
many local steps each round) from the program's own on-device draw under
``participation_key``: the mix's ``participation_seed`` with the run's
seed folded in, so each seed trains and checks its own schedule.  Under
``client_parallel`` every client runs all E steps masked, so the device
work of a round does not depend on the schedule.
"""
from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if not os.path.exists(driver_path(mix)):
        raise ValueError(f"traffic mix {name!r}: no driver for kind "
                         f"{mix.get('kind')!r} under bench/drivers/")
    return mix


def driver_path(mix: dict) -> str:
    return os.path.join(BENCH, "drivers", f"{mix.get('kind')}.py")


def participation_key(mix: dict, seed: int):
    """The key of the participation draws of a run: the mix's
    ``participation_seed`` with every bit of ``seed`` folded in."""
    import jax
    key = jax.random.PRNGKey(int(mix["participation_seed"]))
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
