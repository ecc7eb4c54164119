"""Token-choice top-k MoE: capacity-bounded scatter dispatch over every
expert, or dropless grouped-matmul dispatch over the experts one device
holds (``cfg.moe_experts_held``; ``_moe_ffn_held``).

Design notes (see DESIGN.md §4): the dispatch is pure jnp (scatter/gather),
so it is vmap-safe for the client-parallel federated mode and lowers under
GSPMD with experts sharded over the 'model' axis.  A shard_map all-to-all
variant is the documented hillclimb for the collective-bound MoE pairs.

Router variants:
  softmax  (DeepSeek-V2): softmax scores, top-k renormalised.
  sigmoid  (DeepSeek-V3): sigmoid scores, selection uses score + learned
           bias (aux-loss-free balancing), gates renormalised over top-k.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.weighted_agg import resolve_interpret
from repro.models.common import activation_fn, mlp_apply
from repro.models.sharding import constrain, current_mesh


def _capacity(T: int, k: int, E: int, factor: float) -> int:
    c = int(T * k / E * factor) + 1
    return max(8, -(-c // 8) * 8)  # round up to 8


def moe_ffn(p, x, cfg, ep: bool = False):
    """x: (..., d) -> (..., d), plus scalar aux loss.

    With ``ep=True`` and a production mesh active, dispatches to the
    shard_map expert-parallel path (§Perf: the pure-jnp scatter path makes
    GSPMD all-reduce full (E, cap, d) expert-buffer gradients — ~8.7 TB
    per step on deepseek-v3 train).  ``ep`` must be False under vmap
    (client_parallel training): shard_map's in_specs would bind the
    per-client batch dim to the data axis, which vmap has already claimed
    for the client dim.  Callers (blocks.block_apply) set it from the
    execution context; the jnp path is always a correct fallback.

    p: {"router": (d,E) [, "router_bias": (E,)],
        "experts": {"w_gate","w_up": (E,d,f), "w_down": (E,f,d)},
        ["shared": dense-mlp params]}
    """
    with jax.named_scope("lm.moe"):
        if cfg.moe_experts_held:
            return _moe_ffn_held(p, x, cfg)
        mesh = current_mesh()
        if ep and mesh is not None and "model" in mesh.axis_names \
                and x.ndim == 3 \
                and x.shape[0] % _batch_div(mesh) == 0:
            return _moe_ffn_ep(p, x, cfg, mesh)
        return _moe_ffn_dense(p, x, cfg)


def _batch_div(mesh) -> int:
    import numpy as np
    return int(np.prod([mesh.shape[a] for a in ("pod", "data")
                        if a in mesh.shape]))


def _moe_ffn_dense(p, x, cfg):
    """Reference jnp path (vmap-safe, mesh-free)."""
    orig_shape = x.shape
    d = orig_shape[-1]
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    E, k = cfg.n_experts, cfg.top_k

    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"].astype(jnp.float32)[None, :] \
            if "router_bias" in p else scores
        probs = scores / (jnp.sum(scores, -1, keepdims=True) + 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        scores = probs
        sel = probs
    _, top_i = jax.lax.top_k(sel, k)                       # (T,k)
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)    # (T,k)
    gates = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-9)

    # load-balance aux loss (Switch-style): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)                           # (E,)
    assign = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    ce = assign / (T * k)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    # --- capacity-bounded scatter dispatch -------------------------------
    cap = _capacity(T, k, E, cfg.capacity_factor)
    fe = top_i.reshape(-1)                                 # (T*k,)
    oh = jax.nn.one_hot(fe, E, dtype=jnp.int32)
    pos = (jnp.cumsum(oh, axis=0) - oh)[jnp.arange(T * k), fe]  # rank in e
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)                       # overflow -> pad

    buf = jnp.zeros((E, cap + 1, d), x.dtype)
    buf = buf.at[fe, slot].add(xf[jnp.arange(T * k) // k])
    buf = constrain(buf, P("model", None, None))

    # --- expert FFN (batched over E, sharded over 'model') ---------------
    act = activation_fn(cfg.activation)
    h = act(jnp.einsum("ecd,edf->ecf", buf, p["experts"]["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["experts"]["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["experts"]["w_down"])
    out_buf = constrain(out_buf, P("model", None, None))

    # --- gather + combine -------------------------------------------------
    y_tok = out_buf[fe, slot]                              # (T*k, d)
    y_tok = y_tok * (gates.reshape(-1, 1) * keep[:, None]).astype(x.dtype)
    y = jnp.sum(y_tok.reshape(T, k, d), axis=1)

    if "shared" in p:
        y = y + mlp_apply(p["shared"], xf, cfg)
    return y.reshape(orig_shape), aux


# ---------------------------------------------------------------------------
# shard_map expert-parallel path (§Perf)
# ---------------------------------------------------------------------------


def _routing(xf, p, cfg):
    """Shared router math: returns (top_i (T,k), gates (T,k), aux).  The
    sigmoid router's bias steers the choice only; the chosen scores are
    renormalised and scaled by ``moe_routed_scale``."""
    T = xf.shape[0]
    E, k = cfg.n_experts, cfg.top_k
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"].astype(jnp.float32)[None, :] \
            if "router_bias" in p else scores
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        sel = scores
    _, top_i = jax.lax.top_k(sel, k)
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    gates = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-9)
    gates = gates * cfg.moe_routed_scale
    if not cfg.router_aux_coef:
        return top_i, gates, jnp.float32(0.0)
    if cfg.router_score == "sigmoid":
        probs = scores / (jnp.sum(scores, -1, keepdims=True) + 1e-9)
    else:
        probs = scores
    me = jnp.mean(probs, axis=0)
    assign = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    ce = assign / (T * k)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)
    return top_i, gates, aux


def _moe_ffn_ep(p, x, cfg, mesh):
    """Expert parallelism with replicated activations: every model shard
    routes ALL of its data-shard's tokens, computes only its own E/16
    experts into a local capacity buffer, and the outputs are combined
    with one psum over 'model' (which also carries the TP-sharded shared
    expert).  No cross-shard scatter/gather -> no giant buffer-grad
    all-reduces."""
    E, k = cfg.n_experts, cfg.top_k
    d = x.shape[-1]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    E_l = E // mesh.shape["model"]

    def kernel(xl, router, router_bias, w_gate, w_up, w_down, shared):
        # xl: (b_l, S, d) — replicated across the model row
        midx = jax.lax.axis_index("model")
        xf = xl.reshape(-1, d)
        T_l = xf.shape[0]
        pr = {"router": router}
        if router_bias is not None:
            pr["router_bias"] = router_bias
        top_i, gates, aux = _routing(xf, pr, cfg)
        aux = jax.lax.pmean(aux, batch_axes) if batch_axes else aux

        # local experts only
        lo = midx * E_l
        fe = top_i.reshape(-1) - lo                       # (T_l*k,)
        mine = (fe >= 0) & (fe < E_l)
        fe_c = jnp.where(mine, fe, 0)
        cap = _capacity(T_l, k, E, cfg.capacity_factor)
        oh = jax.nn.one_hot(fe_c, E_l, dtype=jnp.int32) * mine[:, None]
        pos = (jnp.cumsum(oh, axis=0) - oh)[jnp.arange(T_l * k), fe_c]
        keep = mine & (pos < cap)
        slot = jnp.where(keep, pos, cap)

        buf = jnp.zeros((E_l, cap + 1, d), xl.dtype)
        buf = buf.at[fe_c, slot].add(
            xf[jnp.arange(T_l * k) // k] * keep[:, None].astype(xl.dtype))
        act = activation_fn(cfg.activation)
        h = act(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * \
            jnp.einsum("ecd,edf->ecf", buf, w_up)
        out_buf = jnp.einsum("ecf,efd->ecd", h, w_down)
        y_tok = out_buf[fe_c, slot] * \
            (gates.reshape(-1, 1) * keep[:, None]).astype(xl.dtype)
        y = jnp.sum(y_tok.reshape(T_l, k, d), axis=1)

        if shared is not None:
            # shared expert: TP-sharded hidden, partial sum joins the psum
            hs = act(xf @ shared["w_gate"]) * (xf @ shared["w_up"])
            y = y + hs @ shared["w_down"]
        y = jax.lax.psum(y, "model")
        return y.reshape(xl.shape), aux

    P_ = jax.sharding.PartitionSpec
    in_specs = (
        P_(batch_axes if batch_axes else None, None, None),  # x
        P_(None, None),                                      # router
        P_(None) if "router_bias" in p else None,            # bias
        P_("model", None, None), P_("model", None, None),    # w_gate, w_up
        P_("model", None, None),                             # w_down
        {"w_gate": P_(None, "model"), "w_up": P_(None, "model"),
         "w_down": P_("model", None)} if "shared" in p else None,
    )
    out_specs = (P_(batch_axes if batch_axes else None, None, None), P_())
    fn = jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    y, aux = fn(x, p["router"], p.get("router_bias"),
                p["experts"]["w_gate"], p["experts"]["w_up"],
                p["experts"]["w_down"], p.get("shared"))
    return y, aux


# ---------------------------------------------------------------------------
# dropless dispatch over the held experts
# ---------------------------------------------------------------------------

# m-dimension tile of the grouped-matmul kernel: the dispatched rows
# (tokens x top_k) must be a multiple of it for the kernel path
GMM_TILE_M = 128


def gmm_kernel_fits(cfg, rows: int) -> bool:
    """Whether ``grouped_matmul`` over ``rows`` dispatched rows runs as the
    Pallas grouped matmul: the config asks for it (``moe_kernel``), Mosaic
    compiles it (a TPU) and the rows fill whole m tiles."""
    return (bool(cfg.moe_kernel) and not resolve_interpret(None)
            and rows % GMM_TILE_M == 0)


def _gmm_tile(dim: int) -> int:
    """A k or n tile: 512, or the whole dimension below that (the kernel
    masks a ragged last tile)."""
    return min(dim, 512)


def grouped_matmul(lhs, rhs, sizes, *, kernel: bool):
    """Rows of ``lhs`` (m, k), sorted by group, times their group's
    ``rhs`` (n, k, f), in float32.  ``sizes`` (n + 1,) counts each
    group's rows; the last group, rows no held expert takes, has no
    weights and comes back zero.  ``kernel``: the megablox Pallas
    grouped matmul (forward ``gmm``, backward ``gmm`` and ``tgmm``;
    interpreted off the TPU), else ``lax.ragged_dot``."""
    if kernel:
        from jax.experimental.pallas.ops.tpu.megablox import ops
        tiles = (GMM_TILE_M, _gmm_tile(rhs.shape[1]), _gmm_tile(rhs.shape[2]))
        return ops.gmm(lhs, rhs, sizes, jnp.float32, tiles, None, None,
                       False, resolve_interpret(None))
    return jax.lax.ragged_dot(lhs, rhs, sizes[:-1],
                              preferred_element_type=jnp.float32)


@jax.custom_vjp
def _permute_rows(x, perm, inv):
    """x[perm] for a permutation ``perm`` with inverse ``inv``; the
    cotangent goes back by a gather through ``inv``, not a scatter."""
    return jnp.take(x, perm, axis=0)


def _permute_rows_fwd(x, perm, inv):
    return jnp.take(x, perm, axis=0), (perm, inv)


def _permute_rows_bwd(res, g):
    perm, inv = res
    return jnp.take(g, inv, axis=0), None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _moe_ffn_held(p, x, cfg):
    """The held experts' part of the layer, dropless.  Every token is
    routed over all experts; its (token, choice) slots that name a held
    expert are sorted by expert and each expert's rows go through one
    grouped matmul, however many it gets; the other slots add nothing
    (their experts live on other devices).  The shared expert, which
    every device computes alike, is added whole."""
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    T, k = xf.shape[0], cfg.top_k
    n, off = cfg.moe_experts_held, cfg.moe_expert_offset
    kernel = gmm_kernel_fits(cfg, T * k)
    act = activation_fn(cfg.activation)
    with jax.named_scope("lm.moe.route"):
        top_i, gates, aux = _routing(xf, p, cfg)
        e = top_i.reshape(-1) - off
        held = (e >= 0) & (e < n)
        group = jnp.where(held, e, n).astype(jnp.int32)       # (T*k,)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        sizes = jnp.bincount(group, length=n + 1).astype(jnp.int32)
        rows = _permute_rows(jnp.repeat(xf, k, axis=0), order, inv)
        gate = jnp.where(held, gates.reshape(-1), 0.0)        # (T*k,)
    with jax.named_scope("lm.moe.experts"):
        ex = p["experts"]
        h = grouped_matmul(rows, ex["w_up"], sizes, kernel=kernel)
        if "w_gate" in ex:
            h = act(grouped_matmul(rows, ex["w_gate"], sizes,
                                   kernel=kernel)) * h
        else:
            h = act(h)
        out = grouped_matmul(h.astype(x.dtype), ex["w_down"], sizes,
                             kernel=kernel)                   # (T*k, d)
        out = _permute_rows(out, inv, order) * gate[:, None]
        y = jnp.sum(out.reshape(T, k, d), axis=1)
        if "shared" in p:
            y = y + mlp_apply(p["shared"], xf, cfg)
    return y.astype(x.dtype).reshape(x.shape), aux
