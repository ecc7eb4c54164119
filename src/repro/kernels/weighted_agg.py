"""Pallas TPU kernel: Scheme-C weighted client-delta aggregation.

out[d] = sum_k coeffs[k] * deltas[k, d]   (paper Eq. 2 hot loop)

The flattened parameter axis D is tiled into VMEM blocks; each grid step
loads a (KBLK, BLK) tile of client deltas plus the slab's coefficient row
and reduces on-chip (one (1,KBLK)x(KBLK,BLK) f32 MXU matmul per tile).
K (clients per round) is small, so the tile streams at HBM bandwidth —
this kernel turns the aggregation from K separate scaled-add passes into
one fused pass.

One grid, (D/BLK, K/KBLK): the client axis is streamed in KBLK slabs and
accumulated into the revisited output block (init on k==0, += after), so
VMEM stays bounded as K grows.  ``_tiles`` decides BLK and KBLK for every
caller: BLK is DEFAULT_BLOCK, shrunk to D for models smaller than one
tile; KBLK is the whole client axis up to MAX_SINGLE_K (one slab) and
DEFAULT_K_BLOCK beyond it.  Coefficients enter as (K/KBLK, 1, KBLK): a
block whose last two dims equal the array's, which the TPU's (8, 128)
tiling rule always accepts — a (1, KBLK) slice of a (1, K) row with
KBLK=32 is refused by the chip's compiler, though interpret mode runs it.

One entry, two wires.  Without ``scales`` the deltas are any float dtype
(the f32 and bf16 wires).  With ``scales`` they are the int8 codes of
core/compression.py, and the kernel fuses dequantization into the same
reduction: each grid step loads a (KBLK, BLK) int8 tile plus its
(BLK/chunk, KBLK) scale block (scales laid out per K slab with clients
on lanes), spreads the scales over their chunks with an exact f32
product against a 0/1 membership matrix, dequantizes in VMEM and
accumulates coeffs·deltas in f32 — the compressed payload never
materializes as an f32 (K, D) buffer in HBM.

The kernel runs compiled (Mosaic) on a TPU and in the Pallas interpreter
on any other backend (``resolve_interpret``); no caller chooses.

For federations sharded over a mesh, ``weighted_agg_sharded`` runs one
local launch per device over its client slab and finishes with a
cross-device ``psum`` epilogue, so the reduced (D,) vector comes back
replicated on every device without a host round-trip.

Usage::

    out = weighted_agg(coeffs, deltas)                    # (K,),(K,D)->(D,)
    out = weighted_agg(coeffs, codes, scales, chunk=256)  # int8 wire
    out = weighted_agg_sharded(coeffs, deltas, mesh=mesh) # client-sharded K
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

DEFAULT_BLOCK = 2048
# Largest client axis kept fully resident per tile before switching to the
# streamed multi-block K layout.
MAX_SINGLE_K = 64
DEFAULT_K_BLOCK = 32


def resolve_interpret(interpret):
    """None -> interpret only off-TPU (compiled Mosaic on real hardware)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


_HIGHEST = jax.lax.Precision.HIGHEST     # f32 MXU passes, not one bf16
# the zero pad of the operands, named apart from the reduction in a profile
PAD_SCOPE = "weighted_agg.pad"


def _tiles(K: int, D: int, chunk: int | None = None, *,
           block: int | None = None, k_block: int | None = None):
    """(BLK, KBLK) for a (K, D) operand.

    BLK defaults to DEFAULT_BLOCK, shrunk for a model smaller than one
    tile (pad waste); KBLK is the whole client axis up to MAX_SINGLE_K,
    DEFAULT_K_BLOCK slabs beyond it, never taller than K.  On the int8
    wire BLK is a whole number of ``chunk``s, BLK/chunk a multiple of 8
    unless one tile spans every chunk.  An explicit ``block`` or
    ``k_block`` replaces the default.  On TPU an explicit k_block below K
    must be a multiple of the delta dtype's sublane tile (8 for f32, 16
    for bf16)."""
    if block is None:
        block = min(DEFAULT_BLOCK, max(128, -(-D // 128) * 128))
    if k_block is None:
        k_block = K if K <= MAX_SINGLE_K else DEFAULT_K_BLOCK
    if chunk is not None:
        block = min(-(-max(1, block // chunk) // 8) * 8, D // chunk) * chunk
    return block, min(k_block, K)


def _coeff_slabs(coeffs, Kp: int, k_block: int):
    """(K,) -> (Kp/KBLK, 1, KBLK) f32, zero-padded: each K slab's
    coefficients as one full-width row, whose (1, KBLK) block meets the
    TPU's (8, 128) tiling rule at any KBLK (a block dim equal to the
    array dim is always allowed)."""
    c = jnp.pad(coeffs.astype(jnp.float32), (0, Kp - coeffs.shape[0]))
    return c.reshape(Kp // k_block, 1, k_block)


def _accumulate(o_ref, part):
    """Write the K slab's partial sum into the output block revisited
    along the K grid axis (init on the first slab, += after)."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = part

    @pl.when(k > 0)
    def _add():
        o_ref[...] += part


def _agg_kernel(c_ref, d_ref, o_ref):
    """(1, KBLK) coefficient row x (KBLK, BLK) delta tile -> (1, BLK)."""
    _accumulate(o_ref, jnp.dot(c_ref[0], d_ref[...].astype(jnp.float32),
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


@functools.partial(jax.jit, static_argnames=("chunk", "block", "k_block",
                                             "interpret"))
def weighted_agg(coeffs, deltas, scales=None, *, chunk: int | None = None,
                 block: int | None = None, k_block: int | None = None,
                 interpret: bool | None = None):
    """coeffs (K,) f32, deltas (K, D) -> (D,) f32.

    Without ``scales``, deltas is any float dtype.  With ``scales`` (K,
    D/chunk) f32, deltas holds the int8 codes of quantize_chunked, whose
    width D is already a multiple of ``chunk``; the caller slices the
    result back to the un-padded width.  block and k_block override the
    tiles ``_tiles`` picks (tests force the streamed K layout with them).
    """
    K, D = deltas.shape
    if scales is not None:
        if D % chunk:
            raise ValueError(f"payload width {D} not a multiple of the "
                             f"scale chunk {chunk} (quantize_chunked pads)")
        if scales.shape != (K, D // chunk):
            raise ValueError(f"scales shape {scales.shape} != "
                             f"{(K, D // chunk)}")
    block, k_block = _tiles(K, D, None if scales is None else chunk,
                            block=block, k_block=k_block)
    # zero-pad D to whole tiles and K to whole slabs (zero coefficient
    # rows contribute 0)
    pad, kpad = (-D) % block, (-K) % k_block
    Kp, Dp = K + kpad, D + pad
    tile = pl.BlockSpec((k_block, block), lambda i, k: (k, i))
    if scales is None:
        if pad or kpad:
            with jax.named_scope(PAD_SCOPE):
                deltas = jnp.pad(deltas, ((0, kpad), (0, pad)))
        kernel, specs, operands = _agg_kernel, [tile], [deltas]
    else:
        # scales per K slab as (n_slabs, n_chunks, KBLK): a tile's
        # (BC, KBLK) block has the clients on lanes at full width
        bc, n_cp = block // chunk, Dp // chunk
        with jax.named_scope(PAD_SCOPE):
            s = jnp.pad(scales, ((0, kpad), (0, pad // chunk)))
            s = s.reshape(Kp // k_block, k_block, n_cp).transpose(0, 2, 1)
            if pad or kpad:
                deltas = jnp.pad(deltas, ((0, kpad), (0, pad)))
        kernel = functools.partial(_agg_kernel_quant, chunk=chunk)
        specs = [pl.BlockSpec((1, bc, k_block), lambda i, k: (k, i, 0)),
                 tile]
        operands = [s, deltas]
    out = pl.pallas_call(
        kernel,
        grid=(Dp // block, Kp // k_block),
        in_specs=[pl.BlockSpec((1, 1, k_block), lambda i, k: (k, 0, 0))]
        + specs,
        out_specs=pl.BlockSpec((1, block), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(_coeff_slabs(coeffs, Kp, k_block), *operands)
    return out[0, :D]


def _local_agg_psum(coeffs, deltas, *scales, axes, **kw):
    """Per-shard body: reduce the local client slab with one launch, then
    all-reduce the f32 partial sums across the mesh — on the int8 wire
    only compressed bytes shard over the client dim."""
    return jax.lax.psum(weighted_agg(coeffs, deltas, *scales, **kw), axes)


def weighted_agg_sharded(coeffs, deltas, scales=None, *,
                         chunk: int | None = None, mesh, axis="data",
                         k_block: int | None = None):
    """weighted_agg over coeffs (K,), deltas (K, D) and, on the int8
    wire, scales (K, D/chunk), all sharded over ``axis`` of ``mesh`` on
    the client dim -> (D,) f32, replicated over the federation axes.

    ``axis`` names the federation axis — a single mesh axis (``'data'``)
    or a tuple of axes (``('pod', 'data')``) for composite multi-pod
    federations; the client dim then shards over their product.  Each
    device makes one local launch over its (K / n_shards, D) slab, with
    the tiles of its local K (``k_block`` as in weighted_agg), followed
    by a ``psum`` epilogue over exactly the federation axes.  Mesh axes
    *not* named (e.g. ``'model'``) are untouched — each of their shard
    groups runs the same reduction, so downstream code may constrain the
    result back to a model-sharded layout.  K must divide evenly over the
    federation axes (the engine pads capacity so it always does).
    """
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    K = deltas.shape[0]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if K % n:
        raise ValueError(
            f"client axis {K} not divisible by mesh axes {axes!r}={n}; "
            f"pad the client axis (FedSharding.pad_capacity)")
    entry = axes[0] if len(axes) == 1 else axes
    args = (coeffs, deltas) + (() if scales is None else (scales,))
    local = functools.partial(_local_agg_psum, axes=axes, chunk=chunk,
                              k_block=k_block)
    # check_vma=False: shard_map has no replication rule for pallas_call
    specs = (P(entry),) + (P(entry, None),) * (len(args) - 1)
    fn = jax.shard_map(local, mesh=mesh, in_specs=specs, out_specs=P(),
                       check_vma=False)
    return fn(*args)
