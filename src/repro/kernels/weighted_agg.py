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
VMEM stays bounded as K grows.  KBLK is the whole client axis up to
MAX_SINGLE_K (one slab) and DEFAULT_K_BLOCK beyond it.  Coefficients
enter as (K/KBLK, 1, KBLK): a block whose last two dims equal the
array's, which the TPU's (8, 128) tiling rule always accepts — a
(1, KBLK) slice of a (1, K) row with KBLK=32 is refused by the chip's
compiler, though interpret mode runs it.

`interpret=None` auto-detects the backend: compiled Mosaic on TPU,
interpreter on the CPU backend (where the tests run).

For federations sharded over a mesh, `weighted_agg_sharded` runs one local
launch per device over its client slab and finishes with a cross-device
`psum` epilogue, so the reduced (D,) vector comes back replicated on every
device without a host round-trip.

For compressed client deltas (core/compression.py), `weighted_agg_quant`
fuses dequantization into the same reduction: each grid step loads a
(KBLK, BLK) int8 tile plus its (BLK/chunk, KBLK) scale block (scales laid
out per K slab with clients on lanes), spreads the scales over their
chunks with an exact f32 product against a 0/1 membership matrix,
dequantizes in VMEM and accumulates coeffs·deltas in f32 — the
compressed payload never materializes as an f32 (K, D) buffer in HBM.

The tile geometry (DEFAULT_BLOCK / MAX_SINGLE_K / DEFAULT_K_BLOCK) is
env-overridable via REPRO_AGG_BLOCK / REPRO_AGG_MAX_SINGLE_K /
REPRO_AGG_K_BLOCK for real-hardware re-tunes (see docs/engine.md).

Usage::

    out = weighted_agg(coeffs, deltas)                    # (K,),(K,D)->(D,)
    out = weighted_agg_sharded(coeffs, deltas, mesh=mesh) # client-sharded K
    out = weighted_agg_quant(coeffs, payload, scales, chunk=256)  # int8
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P


def _env_int(name: str, default: int) -> int:
    """Tile-geometry override hook (REPRO_AGG_*): real-hardware re-tunes
    should not need code edits.  Read once at import."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {raw!r}") from None
    if val < 1:
        raise ValueError(f"{name} must be >= 1, got {val}")
    return val


DEFAULT_BLOCK = _env_int("REPRO_AGG_BLOCK", 2048)
# Largest client axis kept fully resident per tile before switching to the
# streamed multi-block K layout.
MAX_SINGLE_K = _env_int("REPRO_AGG_MAX_SINGLE_K", 64)
DEFAULT_K_BLOCK = _env_int("REPRO_AGG_K_BLOCK", 32)


def resolve_interpret(interpret):
    """None -> interpret only off-TPU (compiled Mosaic on real hardware)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


_HIGHEST = jax.lax.Precision.HIGHEST     # f32 MXU passes, not one bf16
# the zero pad of the operands, named apart from the reduction in a profile
PAD_SCOPE = "weighted_agg.pad"


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


def _k_block(K: int, k_block: int | None) -> int:
    """Client-slab height: the whole client axis up to MAX_SINGLE_K,
    DEFAULT_K_BLOCK slabs beyond it, never taller than K."""
    if k_block is None:
        k_block = K if K <= MAX_SINGLE_K else DEFAULT_K_BLOCK
    return min(k_block, K)


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret", "k_block"))
def weighted_agg(coeffs, deltas, *, block: int = DEFAULT_BLOCK,
                 interpret: bool | None = None,
                 k_block: int | None = None):
    """coeffs: (K,) f32; deltas: (K, D) any float dtype -> (D,) f32.

    k_block=None picks the layout automatically (single-block K up to
    MAX_SINGLE_K, then DEFAULT_K_BLOCK slabs); pass an explicit k_block to
    force the streamed path.  On TPU an explicit k_block below K must be a
    multiple of the delta dtype's sublane tile (8 for f32, 16 for bf16).
    """
    interpret = resolve_interpret(interpret)
    K, D = deltas.shape
    k_block = _k_block(K, k_block)
    # zero-pad D to whole tiles and K to whole slabs (zero coefficient
    # rows contribute 0)
    pad, kpad = (-D) % block, (-K) % k_block
    if pad or kpad:
        with jax.named_scope(PAD_SCOPE):
            deltas = jnp.pad(deltas, ((0, kpad), (0, pad)))
    Kp, Dp = K + kpad, D + pad
    out = pl.pallas_call(
        _agg_kernel,
        grid=(Dp // block, Kp // k_block),
        in_specs=[
            pl.BlockSpec((1, 1, k_block), lambda i, k: (k, 0, 0)),
            pl.BlockSpec((k_block, block), lambda i, k: (k, i)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        interpret=interpret,
    )(_coeff_slabs(coeffs, Kp, k_block), deltas)
    return out[0, :D]


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


@functools.partial(jax.jit, static_argnames=("chunk", "block", "interpret",
                                             "k_block"))
def weighted_agg_quant(coeffs, payload, scales, *,
                       chunk: int, block: int = DEFAULT_BLOCK,
                       interpret: bool | None = None,
                       k_block: int | None = None):
    """Fused dequant-and-reduce: coeffs (K,) f32, payload (K, Dp) int8,
    scales (K, Dp/chunk) f32 -> (Dp,) f32.

    Dp must already be a multiple of ``chunk`` (quantize_chunked pads);
    the caller slices the result back to the un-padded D.  The grid is
    (D tiles, K slabs) as in weighted_agg.  The scales are laid out per K
    slab as (n_slabs, n_chunks, KBLK), so a tile's (BC, KBLK) scale block
    has the client axis on lanes at full width and its BC = block/chunk
    chunk rows on sublanes.  ``block`` is rounded to a whole number of
    chunks, BC to a multiple of 8 unless one tile spans every chunk.
    """
    interpret = resolve_interpret(interpret)
    K, Dp0 = payload.shape
    if Dp0 % chunk:
        raise ValueError(f"payload width {Dp0} not a multiple of the "
                         f"scale chunk {chunk} (quantize_chunked pads)")
    n_chunks = Dp0 // chunk
    if scales.shape != (K, n_chunks):
        raise ValueError(f"scales shape {scales.shape} != "
                         f"{(K, n_chunks)}")
    bc = min(-(-max(1, block // chunk) // 8) * 8, n_chunks)
    block = bc * chunk
    k_block = _k_block(K, k_block)
    pad_c, kpad = (-n_chunks) % bc, (-K) % k_block
    Kp, n_cp = K + kpad, n_chunks + pad_c
    with jax.named_scope(PAD_SCOPE):
        s = jnp.pad(scales, ((0, kpad), (0, pad_c)))
        s = s.reshape(Kp // k_block, k_block, n_cp).transpose(0, 2, 1)
        if pad_c or kpad:
            payload = jnp.pad(payload, ((0, kpad), (0, pad_c * chunk)))
    Dp = n_cp * chunk
    out = pl.pallas_call(
        functools.partial(_agg_kernel_quant, chunk=chunk),
        grid=(Dp // block, Kp // k_block),
        in_specs=[
            pl.BlockSpec((1, 1, k_block), lambda i, k: (k, 0, 0)),
            pl.BlockSpec((1, bc, k_block), lambda i, k: (k, i, 0)),
            pl.BlockSpec((k_block, block), lambda i, k: (k, i)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        interpret=interpret,
    )(_coeff_slabs(coeffs, Kp, k_block), s, payload)
    return out[0, :Dp0]


def _local_quant_agg_psum(coeffs, payload, scales, *, chunk, axes, block,
                          interpret, k_block):
    """Per-shard body of the quantized sharded path: the compressed slab
    is dequant-reduced locally, and only the f32 (D,) partial crosses
    devices in the psum epilogue — the byte win lands on the wire."""
    out = weighted_agg_quant(coeffs, payload, scales, chunk=chunk,
                             block=block, interpret=interpret,
                             k_block=k_block)
    return jax.lax.psum(out, axes)


def weighted_agg_quant_sharded(coeffs, payload, scales, *, chunk, mesh,
                               axis="data", block: int = DEFAULT_BLOCK,
                               interpret: bool | None = None,
                               k_block: int | None = None):
    """Cross-device weighted_agg_quant: coeffs (K,), payload (K, Dp) int8
    and scales (K, Dp/chunk) sharded over the federation ``axis`` of
    ``mesh`` on the client dim -> (Dp,) f32, replicated.

    Same contract as weighted_agg_sharded (composite axes, K must divide
    the shard count), but each device launches the fused dequant-and-
    reduce kernel over its compressed client slab before the f32 psum.
    """
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    K = payload.shape[0]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if K % n:
        raise ValueError(
            f"client axis {K} not divisible by mesh axes {axes!r}={n}; "
            f"pad the client axis (FedSharding.pad_capacity)")
    entry = axes[0] if len(axes) == 1 else axes
    local = functools.partial(
        _local_quant_agg_psum, chunk=chunk, axes=axes, block=block,
        interpret=resolve_interpret(interpret), k_block=k_block)
    # check_vma=False: shard_map has no replication rule for pallas_call
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(entry), P(entry, None), P(entry, None)),
                       out_specs=P(), check_vma=False)
    return fn(coeffs, payload, scales)


def _local_agg_psum(coeffs, deltas, *, axes, block, interpret, k_block):
    """Per-shard body: reduce the local client slab with one (possibly
    K-tiled) launch, then all-reduce partial sums across the mesh."""
    out = weighted_agg(coeffs, deltas, block=block, interpret=interpret,
                       k_block=k_block)
    return jax.lax.psum(out, axes)


def weighted_agg_sharded(coeffs, deltas, *, mesh, axis="data",
                         block: int = DEFAULT_BLOCK,
                         interpret: bool | None = None,
                         k_block: int | None = None):
    """Cross-device weighted_agg: coeffs (K,) and deltas (K, D) sharded
    over ``axis`` of ``mesh`` on the client dim -> (D,) f32, replicated
    over the federation axes.

    ``axis`` names the federation axis — a single mesh axis (``'data'``)
    or a tuple of axes (``('pod', 'data')``) for composite multi-pod
    federations; the client dim then shards over their product.  Each
    device makes one local launch over its (K / n_shards, D) slab — the
    same single-block/K-tiled layout choice as weighted_agg, applied to
    the local K — followed by a ``psum`` epilogue over exactly the
    federation axes: the flat delta reduction produces global params with
    a single all-reduce and no host round-trip.  Mesh axes *not* named
    (e.g. ``'model'``) are untouched — each of their shard groups runs
    the same reduction, so downstream code may constrain the result back
    to a model-sharded layout.  K must divide evenly over the federation
    axes (the engine pads capacity so it always does).
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
    local = functools.partial(
        _local_agg_psum, axes=axes, block=block,
        interpret=resolve_interpret(interpret), k_block=k_block)
    # check_vma=False: shard_map has no replication rule for pallas_call
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(entry), P(entry, None)),
                       out_specs=P(), check_vma=False)
    return fn(coeffs, deltas)
