"""The check that decides ``correct``, driven on the CPU at a small size.

A run goes through the harness as on the chip (set-up, window, check);
only the look for a chip is skipped.  With the timed path broken
underneath, ``correct`` must come out false: a round that leaves the
parameters unchanged, and local steps that take half of each batch.
The control (the plain reference one step down in precision, put in the
program's place) must fail at least one of the numbers compared."""
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.lib import harness  # noqa: E402
from bench.lib.fedref import DOWN, Policy  # noqa: E402

SMALL = {
    "mnist-static": {"model": {"hidden": [16, 16]},
                     "federation": {"clients": 8},
                     "training": {"span_rounds": 4, "chunk_size": 4,
                                  "agg": "tree"}},
    "mamba2-fed": {"model": {"n_layer": 2, "d_model": 64, "d_state": 16,
                             "headdim": 16, "vocab_size": 256,
                             "vocab_padded": 256, "chunk_size": 16},
                   "federation": {"clients": 2, "samples": 4},
                   "training": {"seq": 64, "agg": "tree"}},
}


# the cells of BENCHMARK.json that have a small size here
CELLS = sorted(set(SMALL) & {w["name"]
                             for w in harness.manifest()["workloads"]})


def _unchanged(orig):
    def local_sgd(loss_fn, params, batches, alpha, eta):
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                            params)
    return local_sgd


def _half_batch(orig):
    def local_sgd(loss_fn, params, batches, alpha, eta):
        half = jax.tree.map(lambda b: b[:, :b.shape[1] // 2], batches)
        return orig(loss_fn, params, half, alpha, eta)
    return local_sgd


def _run(workload):
    return harness.run_cell(workload, 1234567891011, 0.5, False,
                            t_start=time.perf_counter(),
                            overrides=SMALL[workload])


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_broken_round_is_not_correct(workload, fault, monkeypatch):
    from repro.core import fed_step
    monkeypatch.setattr(fed_step, "local_sgd", fault(fed_step.local_sgd))
    out = _run(workload)
    assert out["correct"] is False
    assert list(out)[-1] == "checks"


def test_sound_small_run_is_correct():
    out = _run("mnist-static")
    assert out["correct"] is True
    assert out["checks"]["s_mismatch"]["value"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_a_limit(workload):
    c = harness.Cell(harness.manifest(), workload, 99, SMALL[workload])
    ref = c.reference(Policy())
    pol = Policy(lower=DOWN[c.cfg["model"]["dtype"]])
    snaps, s = c.reference(pol)
    got = harness.check(c, snaps, s, ref)
    assert any(got[k] > lim for k, lim in c.cfg["limits"].items())
