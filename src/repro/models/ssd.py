"""Mamba2 SSD (state-space duality, arXiv:2405.21060), chunked TPU-friendly
form: intra-chunk attention-like matmuls (MXU work) + an inter-chunk
lax.scan over running states.  Decode is the O(1) recurrence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ssd_chunk
from repro.models.common import group_rmsnorm, safe_concat


def segsum(x):
    """x: (..., Q) -> (..., Q, Q) with out[i,j] = sum_{k=j+1..i} x[k] (j<=i),
    -inf above the diagonal."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def chunk_len(chunk: int, S: int) -> int:
    """The chunk ``ssd_chunked`` takes for S positions: ``chunk``, or S
    if shorter, halved until it divides S."""
    Q = min(chunk, S)
    while S % Q:
        Q //= 2
    return Q


def scan_fused(cfg, S: int) -> bool:
    """Whether ``mamba_mixer`` runs its SSD over S positions as the fused
    Pallas op: the config asks for it (``ssm_kernel``) and
    ``ssd_chunk.scan_fits`` takes its shapes on this backend."""
    return bool(cfg.ssm_kernel) and ssd_chunk.scan_fits(
        chunk_len(cfg.ssm_chunk, S), cfg.ssm_n_heads, cfg.ssm_head_dim,
        cfg.ssm_n_groups)


def ssd_chunked(x, dt, A, B, C, chunk: int, h0=None, kernel=False):
    """Chunked SSD scan; its ops carry the device scope ``lm.ssd``.

    With ``kernel`` set, where ``ssd_chunk.scan_fits`` takes the shapes,
    the whole scan is the fused Pallas op ``ssd_chunk.ssd_scan``; the
    jnp path below runs everywhere else.

    x:  (Bb, S, H, P)     head inputs
    dt: (Bb, S, H)        post-softplus step sizes
    A:  (H,)              negative decay rates
    B:  (Bb, S, G, N)     input  projections (G groups, H % G == 0)
    C:  (Bb, S, G, N)     output projections
    h0: (Bb, G, hg, P, N) optional initial state
    Returns (y: (Bb,S,H,P), h_last: (Bb,G,hg,P,N)).
    """
    with jax.named_scope("lm.ssd"):
        Bb, S, H, Pd = x.shape
        G, N = B.shape[2], B.shape[3]
        hg = H // G
        Q = chunk_len(chunk, S)
        nc = S // Q

        f32 = jnp.float32
        if kernel and ssd_chunk.scan_fits(Q, H, Pd, G):
            dA = dt.astype(f32) * A.astype(f32)
            cum = jnp.cumsum(dA.reshape(Bb, nc, Q, H), axis=2)
            if h0 is None:
                h0 = jnp.zeros((Bb, G, hg, Pd, N), f32)
            y, h_last = ssd_chunk.ssd_scan(
                x.reshape(Bb, S, H * Pd), dt, cum.reshape(Bb, S, H), B, C,
                h0, chunk=Q)
            return y.reshape(Bb, S, H, Pd), h_last

        xr = x.reshape(Bb, nc, Q, G, hg, Pd).astype(f32)
        dtr = dt.reshape(Bb, nc, Q, G, hg).astype(f32)
        Br = B.reshape(Bb, nc, Q, G, N).astype(f32)
        Cr = C.reshape(Bb, nc, Q, G, N).astype(f32)

        dA = dtr * A.astype(f32).reshape(G, hg)            # (Bb,nc,Q,G,hg)
        dA_t = jnp.moveaxis(dA, 2, -1)                     # (Bb,nc,G,hg,Q)
        dA_cs = jnp.cumsum(dA_t, axis=-1)                  # (Bb,nc,G,hg,Q)
        dA_sum = dA_cs[..., -1]                            # (Bb,nc,G,hg)

        L = jnp.exp(segsum(dA_t))                          # (Bb,nc,G,hg,Q,Q)
        xdt = xr * dtr[..., None]                          # (Bb,nc,Q,G,hg,P)

        # intra-chunk (the "quadratic / attention" dual form)
        y_intra = jnp.einsum("bcqgn,bcsgn,bcghqs,bcsghp->bcqghp",
                             Cr, Br, L, xdt)

        # chunk-final states
        decay_states = jnp.exp(dA_sum[..., None] - dA_cs)  # (Bb,nc,G,hg,Q)
        x_decay = xdt * jnp.moveaxis(decay_states, -1, 2)[..., None]
        states = jnp.einsum("bcsgn,bcsghp->bcghpn", Br, x_decay)

        # inter-chunk recurrence over running state h
        if h0 is None:
            h0 = jnp.zeros((Bb, G, hg, Pd, N), f32)
        else:
            h0 = h0.astype(f32)
        chunk_decay = jnp.exp(dA_sum)                      # (Bb,nc,G,hg)

        def step(h, inp):
            s_c, dec_c = inp
            h_prev = h
            h = h * dec_c[..., None, None] + s_c
            return h, h_prev

        states_t = jnp.moveaxis(states, 1, 0)              # (nc,Bb,G,hg,P,N)
        decay_t = jnp.moveaxis(chunk_decay, 1, 0)          # (nc,Bb,G,hg)
        h_last, h_prevs = jax.lax.scan(step, h0, (states_t, decay_t))
        h_prevs = jnp.moveaxis(h_prevs, 0, 1)              # (Bb,nc,G,hg,P,N)

        c_in_decay = jnp.exp(dA_cs)                        # (Bb,nc,G,hg,Q)
        y_inter = jnp.einsum("bcqgn,bcghq,bcghpn->bcqghp",
                             Cr, c_in_decay, h_prevs)

        y = (y_intra + y_inter).reshape(Bb, S, H, Pd)
        return y.astype(x.dtype), h_last


def ssd_decode_step(h, x_t, dt_t, A, B_t, C_t):
    """One-token recurrence.
    h: (Bb,G,hg,P,N); x_t: (Bb,H,P); dt_t: (Bb,H); B_t,C_t: (Bb,G,N)."""
    Bb, G, hg, Pd, N = h.shape
    f32 = jnp.float32
    xr = x_t.reshape(Bb, G, hg, Pd).astype(f32)
    dtr = dt_t.reshape(Bb, G, hg).astype(f32)
    dA = jnp.exp(dtr * A.astype(f32).reshape(G, hg))
    h = h.astype(f32) * dA[..., None, None] + jnp.einsum(
        "bgn,bghp->bghpn", B_t.astype(f32), xr * dtr[..., None])
    y = jnp.einsum("bgn,bghpn->bghp", C_t.astype(f32), h)
    return y.reshape(Bb, x_t.shape[1], Pd).astype(x_t.dtype), h


# ---------------------------------------------------------------------------
# Full Mamba2 mixer (in-proj, causal depthwise conv, SSD, gated norm, out)
# ---------------------------------------------------------------------------


def _causal_conv(xBC, w, b):
    """xBC: (Bb,S,Cc); w: (K,Cc); depthwise causal conv."""
    K = w.shape[0]
    S = xBC.shape[1]
    xp = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + S] * w[j] for j in range(K))
    return y + b


def mamba_mixer(p, u, cfg, cache=None, decode=False):
    """Returns (out, updated_cache_or_None).  Its ops carry the device
    scope ``lm.mixer`` (projections, conv, gate, gated norm), with the
    SSD's ``lm.ssd`` nested inside.

    cache: {"conv": (Bb, K-1, Cc) raw pre-conv inputs,
            "state": (Bb, G, hg, P, N)}.
    """
    with jax.named_scope("lm.mixer"):
        d_in = p["in_x"].shape[1]
        Pd = cfg.ssm_head_dim
        H = d_in // Pd
        G, N = cfg.ssm_n_groups, cfg.ssm_d_state
        K = cfg.ssm_d_conv

        z = u @ p["in_z"]
        # safe_concat: in_x's output dim is 'model'-sharded while in_B/in_C
        # stay replicated — a raw concatenate miscompiles under GSPMD here
        # (misaligned shard/piece boundaries; see models/common.safe_concat)
        xBC = safe_concat([u @ p["in_x"], u @ p["in_B"], u @ p["in_C"]],
                          axis=-1)
        dt = jax.nn.softplus((u @ p["in_dt"]).astype(jnp.float32)
                             + p["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(p["A_log"].astype(jnp.float32))

        if decode:
            assert cache is not None
            conv_state = cache["conv"]  # (Bb, K-1, Cc)
            y_conv = (jnp.einsum("bkc,kc->bc", conv_state,
                                 p["conv_w"][: K - 1])
                      + xBC[:, 0] * p["conv_w"][K - 1] + p["conv_b"])
            # safe_concat: the rolling conv cache is replicated while xBC
            # carries the in-proj's 'model' sharding — same mixed-sharding
            # concatenate pattern as the xBC projection above
            new_conv = safe_concat([conv_state[:, 1:], xBC], axis=1)
            xBC_act = jax.nn.silu(y_conv)[:, None, :]      # (Bb,1,Cc)
            x, B_, C_ = jnp.split(xBC_act, [d_in, d_in + G * N], axis=-1)
            y, h = ssd_decode_step(
                cache["state"],
                x[:, 0].reshape(-1, H, Pd),
                dt[:, 0],
                A,
                B_[:, 0].reshape(-1, G, N),
                C_[:, 0].reshape(-1, G, N),
            )
            y = y[:, None]                                  # (Bb,1,H,P)
            new_cache = {"conv": new_conv, "state": h}
        else:
            y_conv = _causal_conv(xBC, p["conv_w"], p["conv_b"])
            xBC_act = jax.nn.silu(y_conv)
            x, B_, C_ = jnp.split(xBC_act, [d_in, d_in + G * N], axis=-1)
            Bb, S = x.shape[0], x.shape[1]
            y, h = ssd_chunked(
                x.reshape(Bb, S, H, Pd), dt, A,
                B_.reshape(Bb, S, G, N), C_.reshape(Bb, S, G, N),
                cfg.ssm_chunk,
                h0=cache["state"] if cache is not None else None,
                kernel=cfg.ssm_kernel,
            )
            new_cache = None
            if cache is not None:  # prefill
                new_conv = xBC[:, -(K - 1):, :]
                new_cache = {"conv": new_conv, "state": h}

        # the D skip in the (Bb, S, H*P) layout x arrives in
        y = (y.reshape(*y.shape[:2], d_in)
             + jnp.repeat(p["D"].astype(y.dtype), Pd) * x)
        y = group_rmsnorm(y * jax.nn.silu(z), p["ssm_norm"],
                          cfg.ssm_norm_groups, cfg.norm_eps)
        return y @ p["out_proj"], new_cache
