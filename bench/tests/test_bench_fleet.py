"""The benchmark's copy of the generators reproduces the program's at
fixed seeds: the data and Table-2 traces the earlier chip smoke ran."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.lib import fedref, fleet  # noqa: E402


@pytest.mark.parametrize("seed", [0, 7])
def test_images_match_program(seed):
    from repro.data import label_sorted_partition, make_class_dataset
    x, y = make_class_dataset(10, 600, seed=seed)
    bx, by = fleet.make_class_dataset(10, 600, seed=seed)
    np.testing.assert_array_equal(x, bx)
    np.testing.assert_array_equal(y, by)
    tr, te = label_sorted_partition(x, y, 100, seed=seed)
    btr, bte = fleet.label_sorted_partition(bx, by, 100, seed=seed)
    for a, b in zip(tr + te, btr + bte):
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])


def test_tokens_match_program():
    from repro.data.tokens import client_token_stream
    for dom in range(4):
        a = client_token_stream(np.random.default_rng(3), 50280, dom, 5000)
        b = fleet.client_token_stream(np.random.default_rng(3), 50280, dom,
                                      5000)
        np.testing.assert_array_equal(a, b)


def test_traces_and_their_law_match_program():
    from repro.core.participation import TRACES
    from repro.fed.engine import trace_cdf_row
    assert [(t.name, t.mean, t.stdev, t.p_inactive) for t in TRACES] == \
        list(fleet.TRACES)
    for t, row in zip(TRACES, fleet.TRACES):
        for E in (2, 5):
            np.testing.assert_array_equal(trace_cdf_row(t, E),
                                          fedref.cdf_row(row, E))


def test_paper_fleet_matches_chip_smoke_clients():
    """image_fleet is chip_smoke.paper_clients: same rows, same traces."""
    from repro.core.participation import TRACES
    fed = {"clients": 100, "classes": 10, "per_class": 600,
           "labels_per_client": 1, "pareto_index": 0.5, "min_samples": 50,
           "max_samples": 400, "holdout": 20}
    clients, _ = fleet.image_fleet(fed, 0)
    from repro.data import label_sorted_partition, make_class_dataset
    x, y = make_class_dataset(10, 600, seed=0)
    train, _ = label_sorted_partition(x, y, 100, seed=0)
    rng = np.random.default_rng(0)
    for c, tr in zip(clients, train):
        want = TRACES[rng.integers(0, len(TRACES))]
        np.testing.assert_array_equal(c["x"], tr[0])
        assert fleet.TRACES[c["trace"]][0] == want.name
