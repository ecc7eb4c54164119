"""Plain reference of the paper's MNIST model: McMahan et al.'s "2NN",
a dense MLP with two hidden ReLU layers, softmax cross-entropy averaged
over the batch.  Float32 throughout; every matmul goes through the
policy (highest precision, or rounded operands for the control).

Parameter layout {"w1", "b1", ..., "wL", "bL"}; weights (fan_in, fan_out)
initialised N(0, 2 / fan_in), biases 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def sizes(cfg) -> list:
    m = cfg["model"]
    return [int(np.prod(m["input_shape"]))] + list(m["hidden"]) + \
        [m["n_classes"]]


def init(key, cfg, policy):
    """All weights from one key in one jitted call, stored as the policy
    says for the configuration's dtype."""
    dims = sizes(cfg)
    dt = policy.store_dtype(cfg["model"]["dtype"])

    @jax.jit
    def make(key):
        ks = jax.random.split(key, len(dims) - 1)
        p = {}
        for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]), start=1):
            p[f"w{i}"] = (jax.random.normal(ks[i - 1], (a, b), F32)
                          * np.sqrt(2.0 / a)).astype(dt)
            p[f"b{i}"] = jnp.zeros((b,), dt)
        return p

    return make(key)


def make_loss(cfg, policy):
    n_layers = len(sizes(cfg)) - 1

    def loss(params, batch):
        x = batch["x"].reshape(batch["x"].shape[0], -1).astype(F32)
        for i in range(1, n_layers + 1):
            x = policy.mm("bi,io->bo", x, params[f"w{i}"]) + \
                policy.rnd(params[f"b{i}"])
            if i < n_layers:
                x = jax.nn.relu(x)
        logp = jax.nn.log_softmax(x, axis=-1)
        picked = jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)
        return -jnp.mean(picked)

    return loss


def make_batch(rows):
    return {"x": rows["x"], "y": rows["y"]}


def stack(cfg, clients):
    """(C, Nmax, ...) buffers, sample counts n and trace ids, as the
    federation's host data says."""
    nmax = cfg["federation"]["max_samples"]
    C = len(clients)
    shape = tuple(cfg["model"]["input_shape"])
    x = np.zeros((C, nmax) + shape, np.float32)
    y = np.zeros((C, nmax), np.int32)
    n = np.zeros(C, np.int32)
    for i, c in enumerate(clients):
        k = len(c["y"])
        x[i, :k], y[i, :k], n[i] = c["x"], c["y"], k
    return {"x": x, "y": y}, n


def probe_batch(cfg, probe):
    x, y = probe
    return {"x": np.asarray(x, np.float32), "y": np.asarray(y, np.int32)}
