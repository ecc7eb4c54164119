"""Pallas TPU kernels for the Mamba2 SSD (state-space duality, arXiv:
2405.21060 §5).

``ssd_scan`` is the whole chunked SSD of ``models.ssd.ssd_chunked`` as one
differentiable op (``jax.custom_vjp``) whose forward and backward passes
are both Pallas kernels.  For head h of group g = h // hg, chunk c of Q
positions, ``cum`` the within-chunk cumsum of dt·A and state h (N, P):

    L[i,j] = exp(cum[i] - cum[j])            for j <= i, else 0
    y      = ((C @ B^T) * L) @ xdt            intra-chunk term   (Q, P)
           + exp(cum)[:, None] * (C @ h)      inter-chunk term
    h     <- exp(cum[-1]) h + B^T @ (exp(cum[-1] - cum)[:, None] * xdt)

with xdt = x * dt.  The grid is (sequence, chunk, head step) and runs the
chunks in order, each head step's running state in VMEM scratch, so the
(Q, Q) decay and score blocks, xdt and the states never reach HBM; the
forward pass writes y and the state after every chunk (the backward
pass's residual), the backward pass runs the chunks in reverse with the
state's gradient in scratch.

Heads are read in the layout the mixer's projection writes, (S, H·P):
a step takes up to ``MAX_HEADS_PER_STEP`` heads of one group, as 128-lane
tiles of 128 / P heads each, so P=64 fills every lane without a relayout.  A tile's heads
share each matmul, and a per-head result keeps its own lanes.  B and C
stay group-shared: C·Bᵀ is formed once per (sequence, chunk, group), and
dB and dC are summed over the group's heads in VMEM before they are
written.

Precision: every elementwise step (segsum, exp, mask, products), the
state and every accumulation is f32.  The matmul operands are rounded as
XLA's default precision rounds the jnp path's f32 einsums: one bf16 pass
on the TPU, none in interpret mode on the CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.weighted_agg import resolve_interpret

LANES = 128
# most heads one grid step of ssd_scan takes
MAX_HEADS_PER_STEP = 8

_NN = (((1,), (0,)), ((), ()))    # a @ b
_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_TN = (((0,), (0,)), ((), ()))    # a.T @ b


def scan_fits(Q: int, H: int, P: int, G: int) -> bool:
    """Whether ``ssd_scan`` takes a chunk of Q positions and H heads of
    size P in G groups on this backend: Mosaic compiles it (a TPU), Q is
    a multiple of the lane width and a group's heads fill whole 128-lane
    tiles.  ``models.ssd.ssd_chunked`` keeps its jnp path elsewhere."""
    if resolve_interpret(None) or Q % LANES or LANES % P or H % G:
        return False
    return (H // G) % (LANES // P) == 0


def _steps(H, P, G):
    """(heads per lane tile, lane tiles per step, head steps, head steps
    per group)."""
    hb = LANES // P
    tiles = (H // G) // hb
    nt = max(d for d in range(1, max(1, MAX_HEADS_PER_STEP // hb) + 1)
             if tiles % d == 0)
    return hb, nt, H // (hb * nt), tiles // nt


def _dot(a, b, dims, dtype):
    return lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                           preferred_element_type=jnp.float32)


def _mask_bias(Q):
    """(Q, Q): 0 on and below the diagonal, -inf above, so that
    exp(diff + bias) is L with its zeros (diff is finite)."""
    i = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    j = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return jnp.where(j <= i, 0.0, -jnp.inf)


class _Tile:
    """One 128-lane tile of a step: its heads h0 .. h0+hb-1, each on P
    lanes, and their columns of the (Q, H) per-head blocks."""

    def __init__(self, h0, hb, P, Q, H):
        self.h0, self.hb, self.P = h0, hb, P
        self.head = lax.broadcasted_iota(jnp.int32, (Q, LANES), 1) // P
        self.lane_h = lax.broadcasted_iota(jnp.int32, (Q, H), 1)

    def col(self, blk, k):
        """Head h0+k's column of a (Q, H) block, (Q, 1), by an exact
        masked lane sum (one nonzero term)."""
        return jnp.sum(jnp.where(self.lane_h == self.h0 + k, blk, 0.0),
                       axis=1, keepdims=True)

    def spread(self, cols):
        """(Q, 1) columns, one per head, onto their heads' lanes."""
        out = jnp.broadcast_to(cols[0], self.head.shape)
        for k in range(1, self.hb):
            out = jnp.where(self.head == k, cols[k], out)
        return out

    def sums(self, a):
        """Per head, the sum of a (Q, 128) value over its lanes: (Q, 1)."""
        return [jnp.sum(jnp.where(self.head == k, a, 0.0), axis=1,
                        keepdims=True) for k in range(self.hb)]

    def totals(self, a):
        """Per head, the sum of an (R, 128) value over its lanes and all
        rows: (1, 1)."""
        head = lax.broadcasted_iota(jnp.int32, a.shape, 1) // self.P
        return [jnp.sum(jnp.sum(jnp.where(head == k, a, 0.0), axis=1,
                                keepdims=True), axis=0, keepdims=True)
                for k in range(self.hb)]

    def put(self, blk, cols):
        """The (Q, H) block with head h0+k's column set to cols[k]."""
        for k, c in enumerate(cols):
            blk = jnp.where(self.lane_h == self.h0 + k, c, blk)
        return blk


def _fwd_kernel(x_ref, dt_ref, cc_ref, cr_ref, b_ref, c_ref, h0_ref,
                y_ref, hout_ref, st_ref, cb_ref, *, P, hb, nt, jg,
                dot_dtype):
    c, j = pl.program_id(1), pl.program_id(2)
    Q, H = dt_ref.shape[1], dt_ref.shape[2]
    f32 = jnp.float32

    @pl.when(c == 0)
    def _():
        st_ref[j] = h0_ref[0]

    @pl.when(j % jg == 0)
    def _():
        cb_ref[...] = _dot(c_ref[0, 0], b_ref[0, 0], _NT, dot_dtype)

    Bm, Cm, cb = b_ref[0, 0], c_ref[0, 0], cb_ref[...]
    dtb, ccb = dt_ref[0], cc_ref[0]
    bias = _mask_bias(Q)
    for t in range(nt):
        lanes = pl.ds(t * LANES, LANES)
        tile = _Tile(j * (hb * nt) + t * hb, hb, P, Q, H)
        ck = [tile.col(ccb, k) for k in range(hb)]
        cum = tile.spread(ck)
        xdt = x_ref[0, :, lanes].astype(f32) * tile.spread(
            [tile.col(dtb, k) for k in range(hb)])
        hprev = st_ref[j, :, lanes]
        y = _dot(Cm, hprev, _NN, dot_dtype) * jnp.exp(cum)
        for k in range(hb):
            L = jnp.exp(ck[k] - cr_ref[0, pl.ds(tile.h0 + k, 1), :] + bias)
            y = y + jnp.where(tile.head == k,
                              _dot(cb * L, xdt, _NN, dot_dtype), 0.0)
        last = cum[Q - 1:Q]
        h = (jnp.exp(last) * hprev
             + _dot(Bm, xdt * jnp.exp(last - cum), _TN, dot_dtype))
        st_ref[j, :, lanes] = h
        hout_ref[0, 0, :, lanes] = h
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)


def _bwd_kernel(x_ref, dt_ref, cc_ref, cr_ref, b_ref, c_ref, h0_ref,
                hp_ref, dy_ref, dhl_ref,
                dx_ref, ddt_ref, dcc_ref, dcr_ref, db_ref, dc_ref, dh0_ref,
                dst_ref, cb_ref, dcb_ref, *, P, hb, nt, jg, dot_dtype):
    c, j = pl.program_id(1), pl.program_id(2)
    Q, H = dt_ref.shape[1], dt_ref.shape[2]
    f32 = jnp.float32
    first_chunk = c == pl.num_programs(1) - 1    # chunks run in reverse

    @pl.when(c == 0)
    def _():
        dst_ref[j] = dhl_ref[0]

    @pl.when(j % jg == 0)
    def _():
        cb_ref[...] = _dot(c_ref[0, 0], b_ref[0, 0], _NT, dot_dtype)
        dcb_ref[...] = jnp.zeros_like(dcb_ref)
        db_ref[0, 0] = jnp.zeros_like(db_ref[0, 0])
        dc_ref[0, 0] = jnp.zeros_like(dc_ref[0, 0])

    Bm, Cm, cb = b_ref[0, 0], c_ref[0, 0], cb_ref[...]
    dtb, ccb = dt_ref[0], cc_ref[0]
    bias = _mask_bias(Q)
    last_row = lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    for t in range(nt):
        lanes = pl.ds(t * LANES, LANES)
        tile = _Tile(j * (hb * nt) + t * hb, hb, P, Q, H)
        ck = [tile.col(ccb, k) for k in range(hb)]
        cum = tile.spread(ck)
        dt = tile.spread([tile.col(dtb, k) for k in range(hb)])
        x = x_ref[0, :, lanes].astype(f32)
        xdt = x * dt
        dy = dy_ref[0, :, lanes].astype(f32)
        g = dst_ref[j, :, lanes]                   # d state after chunk
        hprev = jnp.where(first_chunk, h0_ref[0, :, lanes],
                          hp_ref[0, 0, :, lanes])
        last = cum[Q - 1:Q]
        e_last, ecum = jnp.exp(last), jnp.exp(cum)
        # the state update: h = e_last h_prev + B^T (w * xdt)
        w = jnp.exp(last - cum)
        bg = _dot(Bm, g, _NN, dot_dtype)
        dxdt = w * bg
        xw = xdt * w
        db = _dot(xw, g, _NT, dot_dtype)
        s_state = tile.sums(xw * bg)               # -d cum, + d cum[-1]
        s_carry = tile.totals(g * hprev * e_last)  # + d cum[-1]
        dh = e_last * g
        # the inter-chunk term: y += ecum * (C @ h_prev)
        dye = dy * ecum
        dc = _dot(dye, hprev, _NT, dot_dtype)
        dh = dh + _dot(Cm, dye, _TN, dot_dtype)
        s_inter = tile.sums(dy * _dot(Cm, hprev, _NN, dot_dtype) * ecum)
        # the intra-chunk term: y += (C·Bᵀ * L) @ xdt, per head
        dcb = jnp.zeros((Q, Q), f32)
        s_rows, cols = [], []
        for k in range(hb):
            mine = tile.head == k
            L = jnp.exp(ck[k] - cr_ref[0, pl.ds(tile.h0 + k, 1), :] + bias)
            ds = _dot(jnp.where(mine, dy, 0.0), xdt, _NT, dot_dtype)
            dxdt = dxdt + jnp.where(mine, _dot(cb * L, dy, _TN, dot_dtype),
                                    0.0)
            dsl = ds * L
            dcb = dcb + dsl
            wk = dsl * cb                          # dL * L
            s_rows.append(jnp.sum(wk, axis=0, keepdims=True))
            # d cum[i] = sum_j wk[i, j] - sum_j wk[j, i] (the rows leave
            # through dcr), + the inter term, - the state's weights, and
            # at the last position the state's decay
            cols.append(jnp.sum(wk, axis=1, keepdims=True) + s_inter[k]
                        - s_state[k]
                        + jnp.where(last_row,
                                    jnp.sum(s_state[k], axis=0,
                                            keepdims=True) + s_carry[k],
                                    0.0))
        dcb_ref[...] += dcb
        dcc_ref[0] = tile.put(dcc_ref[0], cols)
        for k in range(hb):
            dcr_ref[0, pl.ds(tile.h0 + k, 1), :] = -s_rows[k]
        ddt_ref[0] = tile.put(ddt_ref[0], tile.sums(dxdt * x))
        dx_ref[0, :, lanes] = (dxdt * dt).astype(dx_ref.dtype)
        db_ref[0, 0] += db
        dc_ref[0, 0] += dc
        dst_ref[j, :, lanes] = dh
        dh0_ref[0, :, lanes] = dh

    @pl.when(j % jg == jg - 1)
    def _():
        dcb = dcb_ref[...]
        dc_ref[0, 0] += _dot(dcb, Bm, _NN, dot_dtype)
        db_ref[0, 0] += _dot(dcb, Cm, _TN, dot_dtype)


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _call(kernel, x, dt, cum, Bt, Ct, h0t, extra, *, Q, interpret,
          reverse):
    """One pallas_call of ``kernel`` over the (sequence, chunk, head step)
    grid, chunks last to first if ``reverse``; ``extra`` are the
    backward pass's (state before the chunk, dy, d h_last)."""
    Bb, S, HP = x.shape
    H, G, N = dt.shape[2], Bt.shape[1], Bt.shape[3]
    P = HP // H
    nc = S // Q
    hb, nt, nj, jg = _steps(H, P, G)
    W = hb * nt * P
    ch = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    heads = pl.BlockSpec((1, Q, W), lambda b, c, j: (b, ch(c), j))
    cols = pl.BlockSpec((1, Q, H), lambda b, c, j: (b, ch(c), 0))
    rows = pl.BlockSpec((1, H, Q), lambda b, c, j: (b, 0, ch(c)))
    group = pl.BlockSpec((1, 1, Q, N), lambda b, c, j: (b, j // jg, ch(c), 0))
    state = pl.BlockSpec((1, N, W), lambda b, c, j: (b, 0, j))
    states = pl.BlockSpec((1, 1, N, W), lambda b, c, j: (b, c, 0, j))
    f32 = jnp.float32
    dot_dtype = jnp.float32 if interpret else jnp.bfloat16
    in_specs = [heads, cols, cols, rows, group, group, state]
    operands = [x, dt, cum, jnp.swapaxes(cum, 1, 2), Bt.astype(dot_dtype),
                Ct.astype(dot_dtype), h0t]
    scratch = [pltpu.VMEM((nj, N, W), f32), pltpu.VMEM((Q, Q), f32)]
    # MXU work a (sequence, chunk): C·Bᵀ per group; per 128-lane tile the
    # inter term and the state update, and one (Q, Q) product per head
    mxu = 2 * Q * (Q * G * N + (H // hb) * LANES * (2 * N + hb * Q))
    if not reverse:
        out_specs = [heads, states]
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                     jax.ShapeDtypeStruct((Bb, nc, N, HP), f32)]
        flops, name = Bb * nc * mxu, "ssd_scan_fwd"
    else:
        # the state before chunk c is the one after chunk c-1 (h0 at 0)
        prev = pl.BlockSpec((1, 1, N, W), lambda b, c, j: (
            b, jnp.maximum(nc - 2 - c, 0), 0, j))
        in_specs += [prev, heads, state]
        operands += list(extra)
        out_specs = [heads, cols, cols, rows, group, group, state]
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                     jax.ShapeDtypeStruct(dt.shape, f32),
                     jax.ShapeDtypeStruct(dt.shape, f32),
                     jax.ShapeDtypeStruct((Bb, H, S), f32),
                     jax.ShapeDtypeStruct(Bt.shape, f32),
                     jax.ShapeDtypeStruct(Bt.shape, f32),
                     jax.ShapeDtypeStruct(h0t.shape, f32)]
        scratch.append(pltpu.VMEM((Q, Q), f32))
        flops, name = 3 * Bb * nc * mxu, "ssd_scan_bwd"   # ~3 per fwd one
    nbytes = sum(a.size * a.dtype.itemsize for a in operands) + sum(
        s.size * jnp.dtype(s.dtype).itemsize for s in out_shape)
    return pl.pallas_call(
        functools.partial(kernel, P=P, hb=hb, nt=nt, jg=jg,
                          dot_dtype=dot_dtype),
        grid=(Bb, nc, nj),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_SEMANTICS,
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=Bb * S * H * Q,
            bytes_accessed=nbytes),
        interpret=interpret,
        name=name,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, cum, Bt, Ct, h0t, Q, interpret):
    return _scan_fwd(x, dt, cum, Bt, Ct, h0t, Q, interpret)[0]


def _scan_fwd(x, dt, cum, Bt, Ct, h0t, Q, interpret):
    y, hout = _call(_fwd_kernel, x, dt, cum, Bt, Ct, h0t, (), Q=Q,
                    interpret=interpret, reverse=False)
    return (y, hout[:, -1]), (x, dt, cum, Bt, Ct, h0t, hout)


def _scan_bwd(Q, interpret, res, cts):
    x, dt, cum, Bt, Ct, h0t, hout = res
    dy, dhl = cts
    dx, ddt, dcc, dcr, db, dc, dh0 = _call(
        _bwd_kernel, x, dt, cum, Bt, Ct, h0t,
        (hout, dy.astype(x.dtype), dhl.astype(jnp.float32)), Q=Q,
        interpret=interpret, reverse=True)
    return (dx, ddt.astype(dt.dtype), (dcc + jnp.swapaxes(dcr, 1, 2)
                                       ).astype(cum.dtype),
            db.astype(Bt.dtype), dc.astype(Ct.dtype), dh0.astype(h0t.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, cum, B, C, h0, *, chunk: int,
             interpret: bool | None = None):
    """The chunked SSD of ``models.ssd.ssd_chunked``, differentiable.

    x:   (Bb, S, H*P) head inputs, in the mixer's layout and dtype
    dt:  (Bb, S, H) float32 step sizes
    cum: (Bb, S, H) float32 cumsum of dt*A within each chunk of ``chunk``
    B, C: (Bb, S, G, N) projections, shared by the H/G heads of a group
    h0:  (Bb, G, H/G, P, N) float32 initial state
    Returns (y: (Bb, S, H*P) in x's dtype, h_last: (Bb, G, H/G, P, N)
    float32).  Gradients flow to all six (cum's as row minus column sums
    of dL * L, plus the inter-chunk and state decays').
    """
    Bb, S, HP = x.shape
    H = dt.shape[2]
    G, N = B.shape[2], B.shape[3]
    P = HP // H
    if S % chunk or HP % H or H % G:
        raise ValueError(f"ssd_scan: {S} positions in chunks of {chunk}, "
                         f"{HP} lanes over {H} heads in {G} groups")
    f32 = jnp.float32
    # states as (N, H*P): each lane tile's heads side by side
    h0t = jnp.moveaxis(h0.astype(f32).reshape(Bb, H, P, N), 3, 1
                       ).reshape(Bb, N, HP)
    y, hl = _scan(x, dt.astype(f32), cum.astype(f32),
                  jnp.swapaxes(B, 1, 2), jnp.swapaxes(C, 1, 2), h0t, chunk,
                  resolve_interpret(interpret))
    h_last = jnp.moveaxis(hl.reshape(Bb, N, H, P), 1, 3)
    return y, h_last.reshape(Bb, G, H // G, P, N)
