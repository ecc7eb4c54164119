"""Plain reference of a federated round of the paper (Eq. 1-2, scheme C).

It imports nothing of the program.  Given the same seed it re-creates
what the program was handed (weights, client data, the participation
key) and replays the rounds in straightforward jax.numpy:

  1. participation: each client's completed local steps s are drawn by
     inverse CDF from its Table-2 trace law (a Beta on the completed
     fraction, rounded to whole steps, with an inactivity atom at 0),
     with the per-round key fold_in(key, tau); batch rows are uniform
     over the client's own samples;
  2. local SGD: E steps of plain SGD with the staircase rate
     eta0 / (tau + 1); step e applies only where e < s;
  3. scheme C: coefficient E p_k / s_k for active clients, 0 otherwise,
     with p_k the client's share of the federation's samples;
  4. aggregation: w <- w + sum_k c_k (w_k - w), in float32.

Clients are taken one after another and their deltas summed into one
float32 accumulator, so memory holds one client's training at a time.
Parameters are stored in the dtype the configuration states (``store``)
and every step computes in float32 at the highest matmul precision.

``Policy`` also makes the control (everything stored and every matmul
operand rounded to the precision below the stated one) and the planted
faults a training cell is checked against.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# one step down in precision: the control of a stated dtype
DOWN = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


@dataclass(frozen=True)
class Policy:
    """How the reference computes.  ``lower``: None for the reference
    itself; for the control, the dtype every matmul operand is rounded
    to, and every parameter is then stored one step below its stated
    dtype (DOWN).  ``fault``: None, or "half_batch" (each step's loss is
    the mean over the first half of its batch)."""
    lower: Optional[str] = None
    fault: Optional[str] = None

    def store_dtype(self, stated):
        stated = jnp.dtype(stated)
        if self.lower is None:
            return stated
        return jnp.dtype(DOWN[stated.name])

    def rnd(self, x):
        if self.lower is None:
            return x
        return x.astype(self.lower).astype(F32)

    def mm(self, spec, *ops):
        """einsum over operands rounded as the policy says."""
        return jnp.einsum(spec, *(self.rnd(o) for o in ops),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=F32)


# -- participation law --------------------------------------------------------
def beta_params(mean: float, stdev: float):
    m, s = mean, max(stdev, 1e-3)
    var = min(s * s, m * (1 - m) * 0.95) if 0 < m < 1 else None
    if var is None or var <= 0:
        return None
    k = m * (1 - m) / var - 1
    return max(m * k, 1e-2), max((1 - m) * k, 1e-2)


def cdf_row(trace, E: int) -> np.ndarray:
    """P(s <= k) for k = 0..E of one (name, mean, stdev, p_inactive)
    trace: s = round(frac * E), frac ~ Beta, at least 1 step when the
    trace has no inactive rounds."""
    from jax.scipy.special import betainc

    _, mean, stdev, q = trace
    ks = np.arange(E + 1)
    ab = beta_params(mean, stdev)
    if ab is None:
        s0 = int(np.clip(np.round(mean * E), 0, E))
        base = (ks >= s0).astype(np.float64)
    else:
        x = np.clip((ks + 0.5) / E, 0.0, 1.0)
        base = np.asarray(betainc(ab[0], ab[1], x), np.float64)
        base[-1] = 1.0
    if q > 0:
        row = q + (1.0 - q) * base
    else:
        row = base.copy()
        row[0] = 0.0
    row[-1] = 1.0
    return row.astype(np.float32)


def sample_round(key, tau, active, n, cdf, E: int, B: int):
    """(s (C,), idx (C, E, B)) of round tau."""
    ks, kb = jax.random.split(jax.random.fold_in(key, tau))
    u = jax.random.uniform(ks, (n.shape[0],))
    s = jnp.sum(u[:, None] > cdf, axis=-1).astype(F32) * active
    ub = jax.random.uniform(kb, (n.shape[0], E, B))
    idx = jnp.minimum((ub * n.astype(F32)[:, None, None]).astype(jnp.int32),
                      n[:, None, None] - 1)
    return s, idx


# -- the rounds -----------------------------------------------------------------
def make_rounds(loss: Callable, make_batch: Callable, *, E: int, B: int,
                eta0: float, policy: Policy):
    """jitted (params, data, n, cdf, key, p, active, tau0, R) -> (params,
    s (R, C)): R rounds from round tau0.  ``loss(params_f32, batch)``
    is the model's plain loss; ``data`` maps buffer name -> (C, Nmax,
    ...) arrays."""

    def local(params, data_c, idx_c, s_c, eta):
        w0 = params

        def step(w, e):
            rows = {k: jnp.take(v, idx_c[e], axis=0)
                    for k, v in data_c.items()}
            batch = make_batch(rows)
            if policy.fault == "half_batch":
                batch = jax.tree.map(lambda a: a[:B // 2], batch)
            wf = jax.tree.map(lambda a: a.astype(F32), w)
            g = jax.grad(loss)(wf, batch)
            new = jax.tree.map(
                lambda a, ga: (a.astype(F32) - eta * ga).astype(a.dtype),
                w, g)
            w = jax.tree.map(lambda a, b: jnp.where(e < s_c, a, b), new, w)
            return w, None

        wE, _ = jax.lax.scan(step, w0, jnp.arange(E))
        return jax.tree.map(lambda a, b: a.astype(F32) - b.astype(F32),
                            wE, w0)

    def one_round(params, xs, data, n, cdf, key, p, active):
        tau = xs
        s, idx = sample_round(key, tau, active, n, cdf, E, B)
        coeff = jnp.where(s > 0, E * p / jnp.maximum(s, 1.0), 0.0)
        eta = jnp.float32(eta0) / jnp.maximum((tau + 1).astype(F32), 1.0)

        def client(acc, c):
            data_c = {k: v[c] for k, v in data.items()}
            d = local(params, data_c, idx[c], s[c], eta)
            return jax.tree.map(lambda a, x: a + coeff[c] * x, acc, d), None

        acc0 = jax.tree.map(lambda a: jnp.zeros(a.shape, F32), params)
        acc, _ = jax.lax.scan(client, acc0, jnp.arange(n.shape[0]))
        new = jax.tree.map(lambda a, x: (a.astype(F32) + x).astype(a.dtype),
                           params, acc)
        return new, s

    def rounds(params, data, n, cdf, key, p, active, tau0, R):
        taus = tau0 + jnp.arange(R, dtype=jnp.int32)
        with jax.default_matmul_precision("highest"):
            return jax.lax.scan(
                lambda w, t: one_round(w, t, data, n, cdf, key, p, active),
                params, taus)

    return jax.jit(rounds, static_argnames=("R",))
