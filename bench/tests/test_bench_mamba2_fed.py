"""The ``mamba2-fed`` cell's configuration: sound at a small size through
the harness as on the chip, at the published parameter count, and with
every limit of its check set."""
import math
import os
import sys
import time

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib import flops, harness  # noqa: E402
from bench.lib.fedref import Policy  # noqa: E402
from test_bench_faults import SMALL  # noqa: E402

CELL = "mamba2-fed"


def _cfg():
    man = harness.manifest()
    return harness.config_of(man, harness.cell_of(man, CELL)["config"])


def test_sound_small_run_is_correct():
    out = harness.run_cell(CELL, 1234567891011, 0.5, False,
                           t_start=time.perf_counter(),
                           overrides=SMALL[CELL])
    assert out["correct"] is True
    assert out["checks"]["s_mismatch"]["value"] == 0


def test_model_block_has_the_published_parameter_count():
    cfg = _cfg()
    m = cfg["model"]
    assert cfg["reduced"] == []
    # the matmul weights flops counts, and the reference's whole tree
    assert flops.mamba2_matmul_params(m) == 128_710_656
    ref = harness.load_module(os.path.join(ROOT, "bench", "configs",
                                           f"{cfg['family']}_ref.py"))
    shapes = jax.eval_shape(lambda k: ref.init(k, cfg, Policy()),
                            jax.random.PRNGKey(0))
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert total == m["n_params"] == 129_100_224


def test_limits_are_set_and_finite():
    limits = _cfg()["limits"]
    assert set(limits) == {"update1_gap", "change3_gap", "loss_gap",
                           "s_mismatch"}
    assert all(math.isfinite(v) for v in limits.values())
    assert limits["s_mismatch"] == 0
    assert all(0 < limits[k] < 1 for k in ("update1_gap", "change3_gap",
                                           "loss_gap"))
