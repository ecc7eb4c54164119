"""Traffic kind ``static``: every client of the configuration is resident
from round 0 and no participation event arrives.  The program is the
program's ``StreamScheduler`` over the whole federation; one call of the
window is ``StreamScheduler.run(span_rounds)``, the call a user makes.

A driver gives the harness:

  program(cell, params)   the system under test, built over the cell's
                          federation with ``params`` as its weights;
  call(cell, prog)        one call of the window; returns the rounds done;
  engine(prog)            the program's RoundEngine (compile counts, K, D);
  params(prog)            the current global parameters, on the device;
  s_rows(prog, a, b)      completed local steps s of rounds a..b, (R, C);
  stats(cell, prog)       further numbers for the metric readers (dict);
  close(prog)             stops what the program started.
"""
from __future__ import annotations

import numpy as np

NO_EVAL = 1 << 30


def program(cell, params):
    from repro.fed import StreamScheduler

    return StreamScheduler(clients=cell.program_clients(),
                           init_params=params,
                           key=cell.participation_key(),
                           **cell.scheduler_kwargs())


def call(cell, prog) -> int:
    prog.run(cell.R, eval_every=NO_EVAL)
    return cell.R


def engine(prog):
    return prog.engine


def params(prog):
    return prog.params


def s_rows(prog, start: int, stop: int) -> np.ndarray:
    return np.stack([h.s for h in prog.history[start:stop]])


def stats(cell, prog) -> dict:
    return {}


def close(prog) -> None:
    prog.close()
