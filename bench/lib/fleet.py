"""The benchmark's own copy of the federation generators.

Copied from the program (``repro.data.images``, ``repro.data.tokens`` and
the Table-2 trace table of ``repro.core.participation``) so that a later
change to the program cannot move the traffic it is measured on.
``bench/tests/test_bench_fleet.py`` pins the copy to the program's
generators at fixed seeds.

Pseudo-MNIST: seeded class-prototype 28x28 images, label-sorted non-IID
partition with Type-I Pareto(0.5) sample counts in [50, 400] (paper
§5.1).  Tokens: per-client Zipf streams through a per-domain permutation
of the vocabulary (non-IID across domains).
"""
from __future__ import annotations

import numpy as np

# The eight device traces of Table 2 of arXiv:2006.06954: five
# CPU-contention levels with no inactive round, three bandwidth levels
# with inactive rounds.  Published: the rows and the stdev of the
# completed fraction (0, 14.8, 11.3, 11.7, 14.8, 23.3, 22.3, 18.3 %).
# Not published in the table: the means and the inactive shares; they
# are the program's reconstruction (repro.core.participation.TRACES),
# decreasing with contention and bandwidth, kept equal to it so that the
# benchmark trains the schedule the program's own tests exercise.
# (name, mean completed fraction, stdev, probability of an inactive round)
TRACES = (
    ("cpu_0", 1.00, 0.000, 0.0),
    ("cpu_30", 0.90, 0.148, 0.0),
    ("cpu_50", 0.75, 0.113, 0.0),
    ("cpu_70", 0.55, 0.117, 0.0),
    ("cpu_90", 0.30, 0.148, 0.0),
    ("bw_low", 0.50, 0.233, 0.30),
    ("bw_med", 0.65, 0.223, 0.20),
    ("bw_high", 0.80, 0.183, 0.10),
)


def make_class_dataset(n_classes: int, n_per_class: int, shape=(28, 28),
                       noise: float = 0.35, seed: int = 0):
    rng = np.random.default_rng(seed)
    protos = rng.normal(0.0, 1.0, size=(n_classes, *shape)).astype(np.float32)
    for _ in range(2):
        protos = (protos + np.roll(protos, 1, axis=1)
                  + np.roll(protos, 1, axis=2)) / 3.0
    xs, ys = [], []
    for c in range(n_classes):
        base = protos[c]
        mix = protos[(c + 1) % n_classes]
        lam = rng.uniform(0.0, 0.25,
                          size=(n_per_class, 1, 1)).astype(np.float32)
        x = (1 - lam) * base + lam * mix
        x = x + rng.normal(0.0, noise,
                           size=(n_per_class, *shape)).astype(np.float32)
        xs.append(x)
        ys.append(np.full(n_per_class, c, np.int32))
    x = np.concatenate(xs)
    y = np.concatenate(ys)
    idx = rng.permutation(len(x))
    return x[idx], y[idx]


def label_sorted_partition(x, y, n_clients: int, labels_per_client: int = 1,
                           seed: int = 0, pareto_index: float = 0.5,
                           min_samples: int = 50, max_samples: int = 400,
                           holdout: int = 20):
    rng = np.random.default_rng(seed)
    by_label = {c: np.nonzero(y == c)[0].tolist() for c in np.unique(y)}
    raw = rng.pareto(pareto_index, size=n_clients) + 1.0
    counts = np.clip((raw * min_samples).astype(int), min_samples,
                     max_samples)
    train, test = [], []
    classes = list(by_label.keys())
    for k in range(n_clients):
        labs = rng.choice(classes, size=labels_per_client, replace=False)
        idxs = []
        need = counts[k] + holdout
        per = -(-need // labels_per_client)
        for lab in labs:
            pool = by_label[int(lab)]
            take = [pool[i % len(pool)] for i in
                    rng.integers(0, len(pool), size=per)]
            idxs.extend(take)
        idxs = np.array(idxs[:need])
        train.append((x[idxs[:-holdout]], y[idxs[:-holdout]]))
        test.append((x[idxs[-holdout:]], y[idxs[-holdout:]]))
    return train, test


def client_token_stream(rng: np.random.Generator, vocab: int, domain: int,
                        n_tokens: int, zipf_a: float = 1.2):
    perm_rng = np.random.default_rng(domain)
    perm = perm_rng.permutation(vocab)
    raw = rng.zipf(zipf_a, size=n_tokens)
    return perm[np.clip(raw, 1, vocab) - 1].astype(np.int32)


def image_fleet(fed: dict, seed: int):
    """The paper's §5.1 federation: per client (x, y, trace index),
    plus the pooled held-out set."""
    x, y = make_class_dataset(fed["classes"], fed["per_class"], seed=seed)
    train, test = label_sorted_partition(
        x, y, fed["clients"], labels_per_client=fed["labels_per_client"],
        seed=seed, pareto_index=fed["pareto_index"],
        min_samples=fed["min_samples"], max_samples=fed["max_samples"],
        holdout=fed["holdout"])
    rng = np.random.default_rng(seed)
    traces = rng.integers(0, len(TRACES), size=fed["clients"])
    clients = [dict(x=tr[0], y=tr[1], trace=int(t))
               for tr, t in zip(train, traces)]
    probe = (np.concatenate([te[0] for te in test]),
             np.concatenate([te[1] for te in test]))
    return clients, probe


def token_fleet(fed: dict, vocab: int, seq: int, seed: int):
    """Non-IID token clients: ``samples`` sequences of seq+1 tokens each,
    Zipf streams over ``domains`` domains round-robin, Table-2 traces
    round-robin; plus one held-out probe sequence batch."""
    rng = np.random.default_rng(seed)
    n = fed["samples"]
    clients = []
    for i in range(fed["clients"]):
        flat = client_token_stream(rng, vocab, i % fed["domains"],
                                   n * (seq + 1), zipf_a=fed["zipf_a"])
        clients.append(dict(x=flat.reshape(n, seq + 1),
                            trace=i % len(TRACES)))
    probe_rng = np.random.default_rng(seed + 1)
    probe = client_token_stream(probe_rng, vocab, 0,
                                fed["probe_rows"] * (seq + 1),
                                zipf_a=fed["zipf_a"])
    return clients, probe.reshape(fed["probe_rows"], seq + 1)


def make(cfg: dict, seed: int):
    """The configuration's federation: (clients, probe) by its
    ``federation.data`` kind."""
    fed = cfg["federation"]
    if fed["data"] == "images":
        return image_fleet(fed, seed)
    if fed["data"] == "tokens":
        return token_fleet(fed, cfg["model"]["vocab_size"],
                           cfg["training"]["seq"], seed)
    raise ValueError(f"unknown federation data {fed['data']!r}")
