"""Pallas TPU kernel: Scheme-C weighted client-delta aggregation.

out[...] = sum_k coeffs[k] * deltas[k, ...]   (paper Eq. 2 hot loop)

One entry, ``weighted_agg(coeffs, x)``, reduces one stacked client-delta
leaf ``x`` of shape (K, *shape) into an f32 aggregate of ``shape``.  The
round calls it once per parameter leaf, on the leaf where it lies: no
(K, D_total) concatenate and no zero-padded copy of the deltas exists.

A leaf of rank >= 3 is viewed as (K, R, M) rows (the leading non-minor
dims merged into R, a free bitcast whenever the second-minor dim fills
whole sublane tiles, e.g. (4, 24, 768, 1536) -> (4, 18432, 1536); the
last two dims swapped first where the second-minor pads fewer bytes on
lanes, e.g. (100, 784, 200) -> (100, 200, 784), as XLA lays it out).  The
grid (R/TR, M/TM, K/KBLK) loads (KBLK, TR, TM) tiles and reduces them on
the VPU: one sublane tile of rows at a time (8 in f32), one f32
multiply-add per client with its
coefficient read from SMEM, so the partial sum stays in vregs.  TM is the
whole minor dim up to MAX_LANES (else a multiple of 128).

A (K, D) operand (a leaf of rank 2, and the int8 wire's codes) keeps the
clients on sublanes: (KBLK, BLK) tiles reduced by one (1, KBLK) x (KBLK,
BLK) f32 MXU matmul.  Coefficients enter as (K/KBLK, 1, KBLK): a block
whose last two dims equal the array's, which the TPU's (8, 128) tiling
rule always accepts.

``_tiles`` sizes the tiles by bytes from K, the leaf's shape and dtype:
one grid step's input and output blocks hold about TILE_BYTES, and the
client axis is taken whole whenever its thinnest tile fits.  Ragged edges
are never padded in HBM: a ragged last row or lane tile is left to
Pallas' masked store (each output element reads only its own column of
deltas), and the clients of a ragged last K slab beyond K are masked out
(an iota compare on the client index), so garbage rows never enter the
sum.  The K slabs accumulate into the output block revisited along the
last grid axis (init on the first slab, += after).

With ``scales`` the deltas are the int8 codes of core/compression.py, a
(K, D) operand over the flattened-leaf chunk grid, and the kernel fuses
dequantization into the same reduction: each grid step loads a (KBLK,
BLK) int8 tile plus its (BLK/chunk, KBLK) scale block (scales laid out
per K slab with clients on lanes), spreads the scales over their chunks
with an exact f32 product against a 0/1 membership matrix, dequantizes in
VMEM and accumulates coeffs·deltas in f32 — the compressed payload never
materializes as an f32 (K, D) buffer in HBM.  Its tiles and its zero pad
to whole tiles are the int8 wire's own (``PAD_SCOPE``).

The kernel runs compiled (Mosaic) on a TPU and in the Pallas interpreter
on any other backend (``resolve_interpret``); no caller chooses.

For federations sharded over a mesh, ``weighted_agg_sharded`` takes the
same leaves: one local launch per leaf on each device over its client
slab, and one cross-device ``psum`` of them all, so the reduced leaves
come back replicated on every device without a host round-trip.

Usage::

    out = weighted_agg(coeffs, leaf)                      # (K,*s) -> s
    out = weighted_agg(coeffs, codes, scales, chunk=256)  # int8 wire
    outs = weighted_agg_sharded(coeffs, leaves, mesh=mesh)  # client-sharded
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

# one grid step's input and output blocks, in VMEM's padded layout
TILE_BYTES = 4 << 20
# widest minor tile of a leaf: one sublane tile of rows of it is the
# partial sum the VPU keeps in vregs
MAX_LANES = 2048
# scoped VMEM for the float path: two buffers of each block plus the MXU
# temporaries of a (K, D) tile
VMEM_LIMIT = 32 << 20
_UNROLL = 4        # clients per step of the VPU loop when K is long
# the int8 wire's tiles: lanes a tile, the largest client axis kept in
# one slab, and the slab height beyond it
INT8_BLOCK = 2048
MAX_SINGLE_K = 64
DEFAULT_K_BLOCK = 32


def resolve_interpret(interpret):
    """None -> interpret only off-TPU (compiled Mosaic on real hardware)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


_HIGHEST = jax.lax.Precision.HIGHEST     # f32 MXU passes, not one bf16
# the int8 wire's zero pad of its operands, named apart from the
# reduction in a profile
PAD_SCOPE = "weighted_agg.pad"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _rows_view(shape, dtype) -> tuple[int, int, bool]:
    """(R, M, swap) of a per-client leaf shape: the leading dims merged
    into R rows of M lanes (a rank-1 shape is one row).  ``swap`` puts
    the second-minor dim on lanes where that pads fewer bytes in the
    TPU's (sublane, 128) tiles, e.g. (784, 200) is read as 200 rows of
    784: XLA lays such a leaf out that way too, so the view is free."""
    if len(shape) == 1:
        return 1, shape[0], False
    sub = 32 // jnp.dtype(dtype).itemsize
    a, b = shape[-2:]
    swap = (_round_up(b, 128) * _round_up(a, sub)
            > _round_up(a, 128) * _round_up(b, sub))
    lead = math.prod(shape[:-2])
    return (lead * a, b, False) if not swap else (lead * b, a, True)


def _tiles(K: int, shape, dtype, chunk: int | None = None, *,
           block: int | None = None, k_block: int | None = None):
    """(KBLK, TR, TM): the client slab, rows and lanes of one tile of a
    (K, *shape) leaf of ``dtype``.

    A rank-1 shape is a (K, D) operand, clients on sublanes (TR = 1); a
    longer one is viewed as (K, R, M) rows (``_rows_view``).  Tiles are
    sized so one grid step's input block and its f32 output block hold
    about TILE_BYTES in VMEM's padded layout; the client axis is whole
    whenever a tile of one sublane tile of rows (or 128 lanes) across it
    fits.  TM is the whole minor dim up to MAX_LANES, else a multiple of
    128; TR is the whole R or a multiple of the sublane tile.  ``block``
    replaces TM of a (K, D) operand and TR of a rows view (rounded to
    the sublane tile), ``k_block`` KBLK.  On the int8 wire (``chunk``)
    the tiles are its own: INT8_BLOCK lanes, a whole number of chunks
    with BLK/chunk a multiple of 8 unless one tile spans every chunk,
    KBLK the whole K up to MAX_SINGLE_K and DEFAULT_K_BLOCK beyond.  On
    TPU a KBLK below K of a (K, D) operand must be a multiple of the
    dtype's sublane tile (8 for f32, 16 for bf16)."""
    if chunk is not None:
        D = shape[0]
        block = block or min(INT8_BLOCK, max(128, _round_up(D, 128)))
        block = min(_round_up(max(1, block // chunk), 8),
                    D // chunk) * chunk
        k_block = k_block or (K if K <= MAX_SINGLE_K else DEFAULT_K_BLOCK)
        return min(k_block, K), 1, block
    size = jnp.dtype(dtype).itemsize
    sub = 32 // size                      # sublanes of one native tile
    R, M, _ = _rows_view(shape, dtype)
    flat = len(shape) == 1
    lanes = M if flat or _round_up(M, 128) <= MAX_LANES else MAX_LANES
    # the thinnest tile: 128 lanes of a (K, D) operand (its f32 row pads
    # to 8 sublanes), one sublane tile of rows of a rows view
    thin = 128 if flat else sub * _round_up(lanes, 128)
    out = 8 * thin if flat else thin
    if k_block is None:
        k_block = K
        if K * thin * size + out * 4 > TILE_BYTES:
            k_block = (TILE_BYTES - out * 4) // (thin * size)
            k_block = k_block // sub * sub if flat else k_block
    k_block = min(k_block, K)
    if flat:
        per_lane = _round_up(k_block, sub) * size + 8 * 4
        block = block or max(128, TILE_BYTES // per_lane // 128 * 128)
        return k_block, 1, M if M <= block else block
    lane_bytes = _round_up(lanes, 128)
    if block is None:
        block = TILE_BYTES // (lane_bytes * (k_block * size + 4))
    block = max(sub, block // sub * sub)
    if R <= block and (R % sub == 0 or R < sub):
        rows = R
    else:
        rows = min(block, max(sub, R // sub * sub))
    return k_block, rows, lanes


def _coeff_slabs(coeffs, Kp: int, k_block: int):
    """(K,) -> (Kp/KBLK, 1, KBLK) f32, zero-padded: each K slab's
    coefficients as one full-width row, whose (1, KBLK) block meets the
    TPU's (8, 128) tiling rule at any KBLK (a block dim equal to the
    array dim is always allowed)."""
    c = jnp.pad(coeffs.astype(jnp.float32), (0, Kp - coeffs.shape[0]))
    return c.reshape(Kp // k_block, 1, k_block)


def _accumulate(o_ref, part):
    """Write the K slab's partial sum into the output block revisited
    along the K grid axis, the last (init on the first slab, += after)."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = part

    @pl.when(k > 0)
    def _add():
        o_ref[...] += part


def _agg_kernel(c_ref, d_ref, o_ref, *, n_clients):
    """(1, KBLK) coefficient row x (KBLK, BLK) delta tile -> (1, BLK), one
    f32 MXU product.  Rows of a ragged last slab (client index >=
    n_clients, an iota compare) are zeroed first: their zero
    coefficients would not cancel a NaN."""
    d = d_ref[...].astype(jnp.float32)
    k_block = d.shape[0]
    if n_clients % k_block:
        client = (jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
                  + pl.program_id(1) * k_block)
        d = jnp.where(client < n_clients, d, 0.0)
    _accumulate(o_ref, jnp.dot(c_ref[0], d,
                               preferred_element_type=jnp.float32,
                               precision=_HIGHEST))


def _agg_kernel_quant(c_ref, s_ref, p_ref, o_ref, *, chunk):
    """Fused dequant-and-reduce tile.  s_ref (1, BC, KBLK) holds the
    scales of the tile's BC chunks for the slab's clients; an exact f32
    product with the 0/1 chunk-membership matrix spreads them to
    (KBLK, BLK), the int8 codes dequantize in VMEM, and the coefficient
    row reduces them as in _agg_kernel.  The compressed payload never
    exists as an f32 (K, D) buffer in HBM."""
    s_t = s_ref[0]                                          # (BC, KBLK)
    bc, blk = s_t.shape[0], p_ref.shape[1]
    start = jax.lax.broadcasted_iota(jnp.int32, (bc, blk), 0) * chunk
    col = jax.lax.broadcasted_iota(jnp.int32, (bc, blk), 1)
    member = ((col >= start) & (col < start + chunk)).astype(jnp.float32)
    scale = jax.lax.dot_general(                            # (KBLK, BLK)
        s_t, member, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST)
    d = p_ref[...].astype(jnp.float32) * scale
    _accumulate(o_ref, jnp.dot(c_ref[0], d,
                               preferred_element_type=jnp.float32,
                               precision=_HIGHEST))


def _rows_kernel(c_ref, x_ref, o_ref, *, n_clients, n_slabs):
    """(KBLK, TR, TM) slab of a leaf's rows -> its (TR, TM) f32 sum, into
    the output block revisited along the K grid axis.

    One sublane tile of rows at a time: each client of the slab adds
    c_k x_k on the VPU in client order, c_k read from SMEM, so the
    partial sum stays in vregs.  Clients of a ragged last slab beyond
    n_clients are masked out: their garbage rows would not cancel."""
    k_block, rows, _ = x_ref.shape
    kb = pl.program_id(2)
    base = kb * k_block
    step = min(rows, 32 // x_ref.dtype.itemsize)     # one sublane tile

    def row_chunk(r):
        def client(k, acc):
            term = c_ref[base + k] * x_ref[k, r, :].astype(jnp.float32)
            if n_clients % k_block:
                term = jnp.where(base + k < n_clients, term, 0.0)
            return acc + term

        def clients(g, acc):
            for u in range(_UNROLL):
                acc = client(g * _UNROLL + u, acc)
            return acc

        acc = jnp.zeros((step, x_ref.shape[2]), jnp.float32)
        if k_block <= 2 * _UNROLL:
            for k in range(k_block):
                acc = client(k, acc)
        else:
            acc = jax.lax.fori_loop(0, k_block // _UNROLL, clients, acc)
            for k in range(k_block - k_block % _UNROLL, k_block):
                acc = client(k, acc)
        if n_slabs == 1:
            o_ref[r, :] = acc
        else:
            @pl.when(kb == 0)
            def _init():
                o_ref[r, :] = acc

            @pl.when(kb > 0)
            def _add():
                o_ref[r, :] += acc

    def body(i, carry):
        row_chunk(pl.ds(pl.multiple_of(i * step, step), step))
        return carry

    if rows == step:
        row_chunk(slice(None))
    else:
        jax.lax.fori_loop(0, rows // step, body, 0)


def _float_params(interpret):
    return None if interpret else pltpu.CompilerParams(
        vmem_limit_bytes=VMEM_LIMIT)


def _agg_2d(coeffs, x, k_block, block, interpret):
    """(K, D) float operand, clients on sublanes -> (D,) f32."""
    K, D = x.shape
    n_k = pl.cdiv(K, k_block)
    out = pl.pallas_call(
        functools.partial(_agg_kernel, n_clients=K),
        grid=(pl.cdiv(D, block), n_k),
        in_specs=[pl.BlockSpec((1, 1, k_block), lambda i, k: (k, 0, 0)),
                  pl.BlockSpec((k_block, block), lambda i, k: (k, i))],
        out_specs=pl.BlockSpec((1, block), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        compiler_params=_float_params(interpret),
        interpret=interpret,
    )(_coeff_slabs(coeffs, n_k * k_block, k_block), x)
    return out[0]


def _agg_rows(coeffs, x, k_block, rows, lanes, interpret):
    """(K, *shape) leaf, rank >= 3, as (K, R, M) rows -> shape f32."""
    K, shape = x.shape[0], x.shape[1:]
    R, M, swap = _rows_view(shape, x.dtype)
    if swap:
        x = jnp.swapaxes(x, -1, -2)
    n_k = pl.cdiv(K, k_block)
    out = pl.pallas_call(
        functools.partial(_rows_kernel, n_clients=K, n_slabs=n_k),
        grid=(pl.cdiv(R, rows), pl.cdiv(M, lanes), n_k),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((k_block, rows, lanes),
                               lambda i, j, k: (k, i, j))],
        out_specs=pl.BlockSpec((rows, lanes), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, M), jnp.float32),
        compiler_params=_float_params(interpret),
        interpret=interpret,
    )(jnp.pad(coeffs.astype(jnp.float32), (0, n_k * k_block - K)),
      x.reshape(K, R, M))
    if not swap:
        return out.reshape(shape)
    return jnp.swapaxes(out.reshape(shape[:-2] + shape[:-3:-1]), -1, -2)


def _agg_int8(coeffs, codes, scales, chunk, k_block, block, interpret):
    """The int8 wire: (K, D) codes and their (K, D/chunk) scales, zero
    padded to whole tiles -> (D,) f32."""
    K, D = codes.shape
    pad, kpad = (-D) % block, (-K) % k_block
    Kp, Dp = K + kpad, D + pad
    # scales per K slab as (n_slabs, n_chunks, KBLK): a tile's (BC, KBLK)
    # block has the clients on lanes at full width
    bc, n_cp = block // chunk, Dp // chunk
    with jax.named_scope(PAD_SCOPE):
        s = jnp.pad(scales, ((0, kpad), (0, pad // chunk)))
        s = s.reshape(Kp // k_block, k_block, n_cp).transpose(0, 2, 1)
        if pad or kpad:
            codes = jnp.pad(codes, ((0, kpad), (0, pad)))
    out = pl.pallas_call(
        functools.partial(_agg_kernel_quant, chunk=chunk),
        grid=(Dp // block, Kp // k_block),
        in_specs=[pl.BlockSpec((1, 1, k_block), lambda i, k: (k, 0, 0)),
                  pl.BlockSpec((1, bc, k_block), lambda i, k: (k, i, 0)),
                  pl.BlockSpec((k_block, block), lambda i, k: (k, i))],
        out_specs=pl.BlockSpec((1, block), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        interpret=interpret,
    )(_coeff_slabs(coeffs, Kp, k_block), s, codes)
    return out[0, :D]


@functools.partial(jax.jit, static_argnames=("chunk", "block", "k_block",
                                             "interpret"))
def weighted_agg(coeffs, deltas, scales=None, *, chunk: int | None = None,
                 block: int | None = None, k_block: int | None = None,
                 interpret: bool | None = None):
    """coeffs (K,) f32, deltas (K, *shape) -> shape f32.

    Without ``scales``, deltas is one leaf of any float dtype and rank
    >= 2, reduced where it lies.  With ``scales`` (K, D/chunk) f32,
    deltas holds the (K, D) int8 codes of quantize_chunked, whose width D
    is already a multiple of ``chunk``; the caller slices the result back
    to the un-padded width.  block and k_block override the tiles
    ``_tiles`` picks (tests force ragged tiles and K slabs with them).
    """
    if deltas.ndim < 2:
        raise ValueError(f"deltas {deltas.shape} has no client axis and "
                         f"leaf: expected (K, *shape)")
    K, shape = deltas.shape[0], deltas.shape[1:]
    interpret = resolve_interpret(interpret)
    if scales is not None:
        D = shape[0] if deltas.ndim == 2 else None
        if D is None or D % chunk:
            raise ValueError(f"payload {deltas.shape} is not (K, D) with D "
                             f"a multiple of the scale chunk {chunk} "
                             f"(quantize_chunked pads)")
        if scales.shape != (K, D // chunk):
            raise ValueError(f"scales shape {scales.shape} != "
                             f"{(K, D // chunk)}")
        k_block, _, block = _tiles(K, shape, deltas.dtype, chunk,
                                   block=block, k_block=k_block)
        return _agg_int8(coeffs, deltas, scales, chunk, k_block, block,
                         interpret)
    k_block, rows, lanes = _tiles(K, shape, deltas.dtype, block=block,
                                  k_block=k_block)
    if deltas.ndim == 2:
        return _agg_2d(coeffs, deltas, k_block, lanes, interpret)
    return _agg_rows(coeffs, deltas, k_block, rows, lanes, interpret)


def _local_agg_psum(coeffs, *operands, axes, scales, **kw):
    """Per-shard body: reduce each local client-slab leaf with its own
    launch (the int8 wire: one launch over codes and scales), then
    all-reduce the f32 partial sums across the mesh in one psum — on the
    int8 wire only compressed bytes shard over the client dim."""
    if scales:
        outs = (weighted_agg(coeffs, *operands, **kw),)
    else:
        outs = tuple(weighted_agg(coeffs, x, **kw) for x in operands)
    return jax.lax.psum(outs, axes)


@functools.partial(jax.jit, static_argnames=("chunk", "mesh", "axis",
                                             "k_block"))
def weighted_agg_sharded(coeffs, deltas, scales=None, *,
                         chunk: int | None = None, mesh, axis="data",
                         k_block: int | None = None):
    """weighted_agg over coeffs (K,) and deltas, a leaf (K, *shape) or a
    list of them (and, on the int8 wire, codes (K, D) with scales (K,
    D/chunk)), all sharded over ``axis`` of ``mesh`` on the client dim
    -> the f32 aggregate of each leaf, in the same structure, replicated
    over the federation axes.

    ``axis`` names the federation axis — a single mesh axis (``'data'``)
    or a tuple of axes (``('pod', 'data')``) for composite multi-pod
    federations; the client dim then shards over their product.  Each
    device makes one local launch per leaf over its (K / n_shards, ...)
    slab, with the tiles of its local K (``k_block`` as in weighted_agg),
    followed by one ``psum`` epilogue of all of them over exactly the
    federation axes.  Mesh axes *not* named (e.g. ``'model'``) are
    untouched — each of their shard groups runs the same reduction, so
    downstream code may constrain the result back to a model-sharded
    layout.  K must divide evenly over the federation axes (the engine
    pads capacity so it always does).
    """
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    leaves, treedef = jax.tree.flatten(deltas)
    K = leaves[0].shape[0]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if K % n:
        raise ValueError(
            f"client axis {K} not divisible by mesh axes {axes!r}={n}; "
            f"pad the client axis (FedSharding.pad_capacity)")
    entry = axes[0] if len(axes) == 1 else axes
    operands = leaves + ([] if scales is None else [scales])
    local = functools.partial(_local_agg_psum, axes=axes,
                              scales=scales is not None, k_block=k_block,
                              **({} if scales is None else {"chunk": chunk}))
    n_out = 1 if scales is not None else len(leaves)
    # check_vma=False: shard_map has no replication rule for pallas_call
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(entry),) * (1 + len(operands)),
                       out_specs=(P(),) * n_out, check_vma=False)
    outs = fn(coeffs, *operands)
    return outs[0] if scales is not None else treedef.unflatten(outs)
