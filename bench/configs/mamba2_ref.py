"""Plain reference of Mamba-2 (Dao & Gu, arXiv:2405.21060) as a causal
language model: token embedding, n_layer pre-norm residual blocks
x + Mixer(RMSNorm(x)), a final RMSNorm and the logits head tied to the
embedding, softmax cross-entropy averaged over every token.

The mixer, as published: one input projection split into z (d_inner),
x (d_inner), B and C (ngroups * d_state each) and dt (nheads); a
depthwise causal convolution of width d_conv with bias over [x, B, C],
then SiLU; dt = softplus(dt + dt_bias); A = -exp(A_log); the SSD
selective state space y_t = sum_{s<=t} C_t.B_s exp(sum_{r=s+1..t} dt_r A)
dt_s x_s, plus the skip D x; the gated RMSNorm of y * silu(z); the output
projection.  The SSD is computed in its quadratic ("attention") form
over the whole sequence, not chunked.  The decay sums are exact within
blocks of ``chunk_size`` positions (the cumulative sums restart there),
so float32 keeps them exact where the decay is not negligible.

Float32 throughout; parameters are stored as the configuration states
(bfloat16 matrices; float32 A_log, D, dt_bias and norm scales).

Layout (the one the program takes): "embed" (vocab_padded, d_model);
"blocks" stacked over layers: "ln1"/"scale", "ssm"/{in_z, in_x, in_B,
in_C, in_dt, conv_w, conv_b, A_log, D, dt_bias, ssm_norm, out_proj};
"final_norm"/"scale".  RMSNorm weights are kept as 1 + scale (scale
initialised at 0); the padded vocabulary rows take no part in the
logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(cfg):
    m = cfg["model"]
    d = m["d_model"]
    d_in = m["expand"] * d
    H = d_in // m["headdim"]
    return dict(d=d, d_in=d_in, H=H, P=m["headdim"], G=m["ngroups"],
                N=m["d_state"], K=m["d_conv"], L=m["n_layer"],
                V=m["vocab_size"], Vp=m["vocab_padded"],
                Q=m["chunk_size"], eps=m["rms_norm_eps"])


def init(key, cfg, policy):
    """All weights from one key in one jitted call: matrices N(0, 0.02)
    (conv 0.1), A = -U(1, 16), dt log-uniform in [1e-3, 1e-1] through
    the inverse softplus, D = 1, biases and norm scales 0."""
    z = dims(cfg)
    wdt = policy.store_dtype(cfg["model"]["dtype"])
    fdt = policy.store_dtype("float32")
    L, d, d_in, H, G, N, K = (z[k] for k in "L d d_in H G N K".split())
    conv_ch = d_in + 2 * G * N

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 10)

        def w(k, shape, scale=0.02):
            return (scale * jax.random.normal(k, shape, F32)).astype(wdt)

        a = jax.random.uniform(ks[8], (L, H), F32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(ks[9], (L, H), F32,
                                        np.log(1e-3), np.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        ssm = {
            "in_z": w(ks[0], (L, d, d_in)),
            "in_x": w(ks[1], (L, d, d_in)),
            "in_B": w(ks[2], (L, d, G * N)),
            "in_C": w(ks[3], (L, d, G * N)),
            "in_dt": w(ks[4], (L, d, H)),
            "conv_w": w(ks[5], (L, K, conv_ch), 0.1),
            "conv_b": jnp.zeros((L, conv_ch), wdt),
            "A_log": jnp.log(a).astype(fdt),
            "D": jnp.ones((L, H), fdt),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(fdt),
            "ssm_norm": jnp.zeros((L, d_in), fdt),
            "out_proj": w(ks[6], (L, d_in, d)),
        }
        return {"embed": w(ks[7], (z["Vp"], d)),
                "blocks": {"ln1": {"scale": jnp.zeros((L, d), fdt)},
                           "ssm": ssm},
                "final_norm": {"scale": jnp.zeros((d,), fdt)}}

    return make(key)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _segsum(a, Q):
    """(..., T) per-position log-decays -> (..., T, T) with
    out[t, s] = sum_{r=s+1..t} a[r] for s <= t, -inf above.  The sums
    restart every Q positions, so pairs within one block subtract
    cumulative sums of at most Q terms."""
    T = a.shape[-1]
    nb = -(-T // Q)
    ap = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, nb * Q - T)])
    blk = ap.reshape(a.shape[:-1] + (nb, Q))
    within = jnp.cumsum(blk, axis=-1)
    base = jnp.cumsum(within[..., -1], axis=-1) - within[..., -1]
    within = within.reshape(a.shape[:-1] + (nb * Q,))[..., :T]
    base = jnp.repeat(base, Q, axis=-1)[..., :T]
    seg = (base[..., :, None] - base[..., None, :]) + \
        (within[..., :, None] - within[..., None, :])
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, seg, -jnp.inf)


def _mixer(p, u, z, mm):
    """u: (Bb, T, d) normalised input -> (Bb, T, d)."""
    Bb, T, _ = u.shape
    H, P, G, N, K, d_in = (z[k] for k in "H P G N K d_in".split())
    gate = mm("btd,de->bte", u, p["in_z"])
    xBC = jnp.concatenate([mm("btd,de->bte", u, p["in_x"]),
                           mm("btd,de->bte", u, p["in_B"]),
                           mm("btd,de->bte", u, p["in_C"])], axis=-1)
    dt = jax.nn.softplus(mm("btd,dh->bth", u, p["in_dt"]) + p["dt_bias"])
    xp = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xp[:, j:j + T] * p["conv_w"][j] for j in range(K))
    xBC = jax.nn.silu(conv + p["conv_b"])
    x = xBC[..., :d_in].reshape(Bb, T, H, P)
    Bm = xBC[..., d_in:d_in + G * N].reshape(Bb, T, G, N)
    Cm = xBC[..., d_in + G * N:].reshape(Bb, T, G, N)
    A = -jnp.exp(p["A_log"])
    hg = H // G
    a = jnp.moveaxis(dt * A, 1, -1)                       # (Bb, H, T)
    decay = jnp.exp(_segsum(a, z["Q"]))                   # (Bb, H, T, T)
    cb = mm("btgn,bsgn->bgts", Cm, Bm)                    # (Bb, G, T, T)
    cb = jnp.repeat(cb, hg, axis=1)                       # (Bb, H, T, T)
    xdt = x * dt[..., None]
    y = mm("bhts,bshp->bthp", cb * decay, xdt)
    y = y + p["D"][:, None] * x
    y = y.reshape(Bb, T, d_in)
    y = _rmsnorm(y * jax.nn.silu(gate), p["ssm_norm"], z["eps"])
    return mm("bte,ed->btd", y, p["out_proj"])


def make_loss(cfg, policy):
    z = dims(cfg)
    mm = policy.mm

    def loss(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        emb = params["embed"]
        x = jnp.take(emb, tokens, axis=0)

        @jax.checkpoint
        def layer(x, p):
            h = _rmsnorm(x, p["ln1"]["scale"], z["eps"])
            return x + _mixer(p["ssm"], h, z, mm), None

        x, _ = jax.lax.scan(layer, x, params["blocks"])
        x = _rmsnorm(x, params["final_norm"]["scale"], z["eps"])
        logits = mm("btd,vd->btv", x, emb[:z["V"]])
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - picked)

    return loss


def make_batch(rows):
    t = rows["tokens"]
    return {"tokens": t[..., :-1], "labels": t[..., 1:]}


def stack(cfg, clients):
    """(C, samples, seq + 1) token buffers and sample counts n."""
    tokens = np.stack([c["x"] for c in clients]).astype(np.int32)
    n = np.full(len(clients), tokens.shape[1], np.int32)
    return {"tokens": tokens}, n


def probe_batch(cfg, probe):
    return make_batch({"tokens": np.asarray(probe, np.int32)})
