"""Plain reference of Nemotron-H (NVIDIA Nemotron-3-Nano, model_type
nemotron_h) as a causal language model, cut to one chip's share.

Token embedding; the layers of ``hybrid_override_pattern`` in order, each
x <- x + f(RMSNorm(x)) with one mixer f; a final RMSNorm; an untied
logits head; softmax cross-entropy averaged over every token.  The
mixers, as published (h the normalised input):

  M  [z | xBC | dt] = h W_in (d_inner | d_inner + 2 G N | heads);
     xBC <- SiLU(causal depthwise conv(xBC) + b); dt <- softplus(dt +
     dt_bias); A = -exp(A_log); y = SSD(x, dt, A, B, C) + D x;
     y <- RMSNorm_g(y * SiLU(z)) over each of G groups of d_inner / G
     channels; f = y W_out.  The SSD in its quadratic ("attention")
     form over the whole sequence, one group of heads at a time under
     jax.checkpoint; its decay sums restart every ``chunk_size``
     positions, so float32 keeps them exact where the decay matters.
  E  s = sigmoid(h W_r) in float32 over all ``model.router_experts``;
     the top-k chosen by s + b_corr (b_corr only steers the choice);
     g = routed_scaling_factor * s_top / sum(s_top); f = sum over the
     held experts e of g_e W_down,e relu(W_up,e h)^2, plus the shared
     expert W_down,sh relu(W_up,sh h)^2.  The held experts are
     ``n_routed_experts`` experts from ``model.expert_offset``; what the
     other experts would add is left out.  Computed densely: every held
     expert over every token, its gate zero where it is not chosen.
  *  causal softmax(Q K^T / sqrt(head_dim)) V, grouped-query (query head
     i reads KV head i // (heads / KV heads)), no bias, no rotary
     embedding, then o_proj; a block of query heads at a time under
     jax.checkpoint.

Float32 throughout at the highest matmul precision; parameters stored as
the configuration states (bfloat16 matrices; float32 router, its bias,
A_log, D, dt_bias and norm scales).

Layout (the one the program takes): "embed" (vocab, d); "ssm_blocks",
"expert_blocks", "attn_blocks", each stacked over that kind's layers in
pattern order: "ln1"/"scale" and "ssm"/{in_z, in_x, in_B, in_C, in_dt,
conv_w, conv_b, A_log, D, dt_bias, ssm_norm, out_proj}, "moe"/{router,
router_bias, experts/{w_up, w_down}, shared/{w_up, w_down}} or
"attn"/{wq, wk, wv, wo}; "final_norm"/"scale"; "lm_head" (d, vocab).
RMSNorm weights are kept as 1 + scale (scale initialised at 0).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
KINDS = {"M": "ssm_blocks", "E": "expert_blocks", "*": "attn_blocks"}


def dims(cfg):
    m = cfg["model"]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return dict(d=cfg["hidden_size"], pattern=cfg["hybrid_override_pattern"],
                H=H, P=P, d_in=H * P, G=cfg["n_groups"],
                N=cfg["ssm_state_size"], K=cfg["conv_kernel"],
                Q=cfg["chunk_size"], E=m["router_experts"],
                held=cfg["n_routed_experts"], off=m["expert_offset"],
                k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
                fs=cfg["moe_shared_expert_intermediate_size"],
                scale=cfg["routed_scaling_factor"],
                Hq=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], V=m["vocab_size"],
                eps=cfg["layer_norm_epsilon"])


def init(key, cfg, policy):
    """All weights from one key in one jitted call: matrices N(0, 0.02),
    the router N(0, 0.006), conv 0.1, A = -U(1, 16), dt log-uniform in
    [1e-3, 1e-1] through the inverse softplus, D = 1, biases, the
    router's correction bias and norm scales 0."""
    z = dims(cfg)
    wdt = policy.store_dtype(cfg["model"]["dtype"])
    fdt = policy.store_dtype("float32")
    d, d_in, H, G, N, K = (z[k] for k in "d d_in H G N K".split())
    conv_ch = d_in + 2 * G * N
    nM, nE, nA = (z["pattern"].count(c) for c in "ME*")

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 24))

        def w(shape, scale=0.02, dtype=wdt):
            return (scale * jax.random.normal(next(ks), shape, F32)
                    ).astype(dtype)

        def norm(L, width=d):
            return {"scale": jnp.zeros((L, width), fdt)}

        a = jax.random.uniform(next(ks), (nM, H), F32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(next(ks), (nM, H), F32,
                                        np.log(1e-3), np.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        out = {"embed": w((z["V"], d)),
               "final_norm": {"scale": jnp.zeros((d,), fdt)},
               "lm_head": w((d, z["V"]))}
        if nM:
            out["ssm_blocks"] = {"ln1": norm(nM), "ssm": {
                "in_z": w((nM, d, d_in)),
                "in_x": w((nM, d, d_in)),
                "in_B": w((nM, d, G * N)),
                "in_C": w((nM, d, G * N)),
                "in_dt": w((nM, d, H)),
                "conv_w": w((nM, K, conv_ch), 0.1),
                "conv_b": jnp.zeros((nM, conv_ch), wdt),
                "A_log": jnp.log(a).astype(fdt),
                "D": jnp.ones((nM, H), fdt),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(fdt),
                "ssm_norm": jnp.zeros((nM, d_in), fdt),
                "out_proj": w((nM, d_in, d)),
            }}
        if nE:
            n, f, fs = z["held"], z["f"], z["fs"]
            out["expert_blocks"] = {"ln1": norm(nE), "moe": {
                "router": w((nE, d, z["E"]), 0.006, fdt),
                "router_bias": jnp.zeros((nE, z["E"]), fdt),
                "experts": {"w_up": w((nE, n, d, f)),
                            "w_down": w((nE, n, f, d))},
                "shared": {"w_up": w((nE, d, fs)),
                           "w_down": w((nE, fs, d))},
            }}
        if nA:
            qd, kd = z["Hq"] * z["hd"], z["Hkv"] * z["hd"]
            out["attn_blocks"] = {"ln1": norm(nA), "attn": {
                "wq": w((nA, d, qd)), "wk": w((nA, d, kd)),
                "wv": w((nA, d, kd)), "wo": w((nA, qd, d))}}
        return out

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


def _ssd(x, dt, A, Bm, Cm, Q, mm):
    """y_t = sum_{s<=t} C_t.B_s exp(sum_{r=s+1..t} dt_r A) dt_s x_s, one
    group of heads (sharing B and C) at a time.  x (Bb, T, H, P), dt
    (Bb, T, H), A (H,), Bm and Cm (Bb, T, G, N)."""
    Bb, T, H, P = x.shape
    G = Bm.shape[2]
    hg = H // G

    @jax.checkpoint
    def group(args):
        xg, dtg, ag, bg, cg = args        # (Bb,T,hg,P) (Bb,T,hg) (hg,) (Bb,T,N)
        decay = jnp.exp(_segsum(jnp.moveaxis(dtg * ag, 1, -1), Q))
        cb = mm("btn,bsn->bts", cg, bg)                   # (Bb, T, T)
        return mm("bhts,bshp->bthp", cb[:, None] * decay,
                  xg * dtg[..., None])

    ys = jax.lax.map(group, (
        jnp.moveaxis(x.reshape(Bb, T, G, hg, P), 2, 0),
        jnp.moveaxis(dt.reshape(Bb, T, G, hg), 2, 0),
        A.reshape(G, hg),
        jnp.moveaxis(Bm, 2, 0), jnp.moveaxis(Cm, 2, 0)))
    return jnp.moveaxis(ys, 0, 2).reshape(Bb, T, H, P)


def _mamba(p, u, z, mm):
    """M: u (Bb, T, d) normalised input -> (Bb, T, d)."""
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
    y = _ssd(x, dt, -jnp.exp(p["A_log"]), Bm, Cm, z["Q"], mm)
    y = (y + p["D"][:, None] * x).reshape(Bb, T, G, d_in // G)
    g = (y * jax.nn.silu(gate).reshape(y.shape))
    y = _rmsnorm(g, p["ssm_norm"].reshape(G, d_in // G), z["eps"])
    return mm("bte,ed->btd", y.reshape(Bb, T, d_in), p["out_proj"])


def _experts(p, u, z, mm):
    """E: the held experts' part and the shared expert, densely."""
    Bb, T, d = u.shape
    x = u.reshape(Bb * T, d)
    s = jax.nn.sigmoid(mm("td,de->te", x, p["router"]))
    _, top = jax.lax.top_k(s + p["router_bias"], z["k"])
    ts = jnp.take_along_axis(s, top, axis=-1)
    g = z["scale"] * ts / jnp.sum(ts, axis=-1, keepdims=True)

    def relu2(a):
        return jnp.square(jax.nn.relu(a))

    def expert(y, e):
        w_up, w_down, idx = e
        ge = jnp.sum(jnp.where(top == idx, g, 0.0), axis=-1)
        h = relu2(mm("td,df->tf", x, w_up))
        return y + ge[:, None] * mm("tf,fd->td", h, w_down), None

    sh = p["shared"]
    y = mm("tf,fd->td", relu2(mm("td,df->tf", x, sh["w_up"])), sh["w_down"])
    ex = p["experts"]
    idx = z["off"] + jnp.arange(z["held"])
    y, _ = jax.lax.scan(expert, y, (ex["w_up"], ex["w_down"], idx))
    return y.reshape(Bb, T, d)


def _attention(p, u, z, mm):
    """*: causal grouped-query attention, no rotary embedding."""
    Bb, T, _ = u.shape
    Hq, Hkv, hd = z["Hq"], z["Hkv"], z["hd"]
    rep = Hq // Hkv
    blk = math.gcd(4, rep)                   # query heads of one KV head
    nb = Hq // blk
    q = mm("btd,de->bte", u, p["wq"]).reshape(Bb, T, nb, blk, hd)
    k = mm("btd,de->bte", u, p["wk"]).reshape(Bb, T, Hkv, hd)
    v = mm("btd,de->bte", u, p["wv"]).reshape(Bb, T, Hkv, hd)
    kv = (np.arange(nb) * blk) // rep        # each block's KV head
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def block(args):
        qb, kb, vb = args                    # (Bb,T,blk,hd) (Bb,T,hd)
        sc = mm("bthd,bsd->bhts", qb, kb) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return mm("bhts,bsd->bthd", w, vb)

    o = jax.lax.map(block, (jnp.moveaxis(q, 2, 0),
                            jnp.moveaxis(k[:, :, kv], 2, 0),
                            jnp.moveaxis(v[:, :, kv], 2, 0)))
    o = jnp.moveaxis(o, 0, 2).reshape(Bb, T, Hq * hd)
    return mm("bte,ed->btd", o, p["wo"])


MIXERS = {"M": ("ssm", _mamba), "E": ("moe", _experts),
          "*": ("attn", _attention)}


def hidden(params, tokens, z, mm):
    """The final-normed hidden states (Bb, T, d) of the layer pattern."""
    x = jnp.take(params["embed"], tokens, axis=0)
    seen = {}
    for ch in z["pattern"]:
        j = seen[ch] = seen.get(ch, -1) + 1
        key, mixer = MIXERS[ch]
        blocks = params[KINDS[ch]]
        p = jax.tree.map(lambda a: a[j], {"ln1": blocks["ln1"],
                                          key: blocks[key]})

        @jax.checkpoint
        def layer(x, p, mixer=mixer, key=key):
            h = _rmsnorm(x, p["ln1"]["scale"], z["eps"])
            return x + mixer(p[key], h, z, mm)

        x = layer(x, p)
    return _rmsnorm(x, params["final_norm"]["scale"], z["eps"])


def logits(cfg, policy, params, tokens):
    z = dims(cfg)
    return policy.mm("btd,dv->btv", hidden(params, tokens, z, policy.mm),
                     params["lm_head"])


def make_loss(cfg, policy):
    def loss(params, batch):
        lg = logits(cfg, policy, params, batch["tokens"])
        logz = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, batch["labels"][..., None],
                                     axis=-1)[..., 0]
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
