"""Per-kernel allclose vs the pure-jnp oracle, swept over shapes/dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.weighted_agg import weighted_agg

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("K", [1, 3, 8, 32])
@pytest.mark.parametrize("D", [64, 1000, 4096, 10001])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weighted_agg_matches_ref(K, D, dtype):
    k1, k2 = jax.random.split(KEY)
    c = jax.random.uniform(k1, (K,), jnp.float32)
    d = jax.random.normal(k2, (K, D), dtype)
    got = weighted_agg(c, d)
    want = ref.weighted_agg_ref(c, d)
    np.testing.assert_allclose(got, want, rtol=2e-2 if dtype == jnp.bfloat16
                               else 1e-6, atol=1e-5)


@pytest.mark.parametrize("shape,kw", [
    ((100, 784, 200), {}), ((100, 200, 10), {}), ((100, 10), {}),  # mnist
    ((4, 24, 768, 24), {}), ((4, 24, 4, 1792), {}),              # mamba2
    ((4, 24, 24), {}),
    ((5, 20, 130), {"block": 8}),        # ragged last row tile
    ((7, 16, 300), {"k_block": 3}),      # ragged last K slab
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple)
    else "-".join(f"{k}{n}" for k, n in v.items()) or "auto")
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weighted_agg_leaf_matches_ref(shape, kw, dtype):
    """One stacked leaf (K, *shape) of rank 2-4 reduced where it lies ==
    the jnp reduction, at the cells' awkward leaves (minor dims of 10,
    24 and 200; a second-minor of 4) and with ragged row tiles and K
    slabs, whose garbage rows and clients must not enter the sum."""
    rng = np.random.default_rng(sum(shape))
    c = jnp.asarray(rng.uniform(0, 2, shape[0]), jnp.float32)
    d = jnp.asarray(rng.normal(size=shape), dtype)
    got = weighted_agg(c, d, **kw)
    want = ref.weighted_agg_ref(c, d)
    assert got.shape == shape[1:] and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("case", range(4))
def test_chip_smoke_leaf_cases_run_off_chip(case):
    """chip_smoke.py's kernel check at each of its leaf cases (forced
    ragged tiles among them) runs in the interpreter and meets its
    bound: the tiles reach the kernel, never the reference."""
    import chip_smoke
    K, shape, tiles = chip_smoke.LEAF_CASES[case]
    err = chip_smoke.kernel_parity("none", K, shape, **tiles)
    assert err <= chip_smoke.KERNEL_BOUND


@pytest.mark.parametrize("K,k_block", [(8, 4), (32, 8), (48, 32), (7, 2)])
@pytest.mark.parametrize("D", [256, 5000])
def test_weighted_agg_tiled_k_matches_ref(K, k_block, D):
    """Streamed multi-block K path (client axis in k_block slabs,
    accumulated across the second grid dim) == single-block reference."""
    k1, k2 = jax.random.split(KEY)
    c = jax.random.uniform(k1, (K,), jnp.float32)
    d = jax.random.normal(k2, (K, D), jnp.float32)
    got = weighted_agg(c, d, k_block=k_block)
    want = ref.weighted_agg_ref(c, d)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_weighted_agg_auto_tiles_large_k():
    """K beyond the int8 wire's MAX_SINGLE_K: the float path keeps the
    whole client axis in one tile, whose K is not a sublane multiple."""
    from repro.kernels.weighted_agg import MAX_SINGLE_K, _tiles
    K = MAX_SINGLE_K + 9
    assert _tiles(K, (3000,), jnp.float32)[0] == K
    k1, k2 = jax.random.split(KEY)
    c = jax.random.uniform(k1, (K,), jnp.float32)
    d = jax.random.normal(k2, (K, 3000), jnp.float32)
    got = weighted_agg(c, d)
    want = ref.weighted_agg_ref(c, d)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_weighted_agg_backend_aware_interpret_default():
    """interpret=None resolves from the backend: interpret mode everywhere
    except TPU (so the CPU CI container runs without Mosaic)."""
    from repro.kernels.weighted_agg import resolve_interpret
    expected = jax.default_backend() != "tpu"
    assert resolve_interpret(None) == expected
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    # and the public wrapper works with no interpret argument at all
    c = jax.random.uniform(KEY, (4,), jnp.float32)
    d = jax.random.normal(KEY, (4, 300), jnp.float32)
    np.testing.assert_allclose(weighted_agg(c, d),
                               ref.weighted_agg_ref(c, d),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(K=st.integers(1, 16), D=st.integers(1, 3000),
       block=st.sampled_from([128, 512, 2048]))
def test_weighted_agg_property(K, D, block):
    rng = np.random.default_rng(K * 1000 + D)
    c = jnp.asarray(rng.uniform(0, 2, K), jnp.float32)
    d = jnp.asarray(rng.normal(size=(K, D)), jnp.float32)
    got = weighted_agg(c, d, block=block)
    want = ref.weighted_agg_ref(c, d)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("K,shape,tiles", [
    (4, (1,), (4, 1, 1)), (4, (127,), (4, 1, 127)),
    (4, (1_000_000,), (4, 1, 65536)),
    (100, (784, 200), (100, 8, 784)),    # mnist-static: w1 as 200 rows
    (4, (24, 768, 1536), (4, 136, 1536)),  # mamba2-fed: in_x
])
def test_tiles_pin_geometry(K, shape, tiles):
    """The kernel's own (KBLK, TR, TM) for an f32 leaf (K, *shape): the
    whole client axis, the whole minor dim (a (K, D) operand's D up to
    ~4 MiB of tile), rows filling ~4 MiB with the f32 output block, and
    mnist's (784, 200) read with its 784 on lanes."""
    from repro.kernels.weighted_agg import _tiles
    assert _tiles(K, shape, jnp.float32) == tiles


def _quantized(K, D, chunk, seed=0):
    from repro.core.compression import quantize_chunked
    rng = np.random.default_rng(seed)
    c = jnp.asarray(rng.uniform(0, 1, K), jnp.float32)
    flat = jnp.asarray(rng.normal(size=(K, D)) * 0.3, jnp.float32)
    payload, scales = quantize_chunked(flat, chunk=chunk)
    return c, payload, scales


@pytest.mark.parametrize("K,k_block", [(1, None), (8, None), (32, 8),
                                       (70, None)])  # 70 > MAX_SINGLE_K
@pytest.mark.parametrize("D,chunk", [(256, 64), (1000, 128), (4096, 256)])
def test_weighted_agg_quant_matches_ref(K, k_block, D, chunk):
    """Fused dequant-and-reduce == dequantize-then-reduce oracle, across
    chunk geometries, the streamed multi-block-K layout, and the
    auto-tiled large-K path."""
    c, payload, scales = _quantized(K, D, chunk)
    got = weighted_agg(c, payload, scales, chunk=chunk, k_block=k_block)
    want = ref.weighted_agg_ref(c, payload, scales, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_weighted_agg_quant_block_not_chunk_aligned():
    """block is re-floored to a chunk multiple internally; a block
    smaller than chunk must still work (clamped up to one chunk)."""
    c, payload, scales = _quantized(4, 2048, 256)
    want = ref.weighted_agg_ref(c, payload, scales, chunk=256)
    for block in (300, 128, 512):
        got = weighted_agg(c, payload, scales, chunk=256, block=block)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_weighted_agg_quant_rejects_bad_shapes():
    c, payload, scales = _quantized(4, 512, 128)
    with pytest.raises(ValueError):
        weighted_agg(c, payload, scales[:, :-1], chunk=128)
    with pytest.raises(ValueError):
        weighted_agg(c, payload[:, :-1], scales, chunk=128)


def test_weighted_agg_quant_never_materializes_f32_deltas():
    """The acceptance criterion of the fused path: no f32 tensor of the
    full (K, D) payload size exists outside the pallas_call — the
    dequantized deltas live only in VMEM tiles."""
    K, D, chunk = 8, 4096, 256
    c, payload, scales = _quantized(K, D, chunk)
    jaxpr = jax.make_jaxpr(
        lambda c, p, s: weighted_agg(c, p, s, chunk=chunk))(
        c, payload, scales)

    def walk(jxp):
        for eqn in jxp.eqns:
            if eqn.primitive.name == "pallas_call":
                continue                  # VMEM tiles are allowed
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is None or not hasattr(aval, "shape"):
                    continue
                if (aval.dtype == jnp.float32
                        and int(np.prod(aval.shape or (1,))) >= K * D):
                    raise AssertionError(
                        f"f32 {aval.shape} materialized by "
                        f"{eqn.primitive.name}")
            for val in eqn.params.values():
                if hasattr(val, "eqns"):                # Jaxpr
                    walk(val)
                elif hasattr(val, "jaxpr"):             # ClosedJaxpr
                    walk(val.jaxpr)
    walk(jaxpr.jaxpr)


@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 2, 2, 128, 64),
    (2, 4, 2, 256, 64),
    (1, 4, 1, 384, 128),   # MQA, non-pow2 blocks coverage
    (2, 2, 2, 100, 32),    # padded seq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, H, KV, S, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    got = flash_attention(q, k, v)
    kr = jnp.repeat(k, H // KV, 1)
    vr = jnp.repeat(v, H // KV, 1)
    want = ref.flash_attention_ref(q, kr, vr)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 2e-5,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_flash_attention_non_causal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 128, 64))
    v = jax.random.normal(ks[2], (1, 2, 128, 64))
    got = flash_attention(q, k, v, causal=False)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)


# --- ssd_scan: the whole chunked SSD as one op with its backward pass -----
# Q=128 tiles on the chip; hg > 1 heads share each group's B and C.

SCAN_Q = 128


def _scan_inputs(Bb, S, H, P, G, N, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (f(Bb, S, H, P),
            jnp.asarray(rng.uniform(0.01, 0.2, (Bb, S, H)), jnp.float32),
            jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32),
            f(Bb, S, G, N), f(Bb, S, G, N), f(Bb, G, H // G, P, N))


def _scan_of_ssd_chunked(x, dt, A, B, C, h0):
    from repro.models.ssd import ssd_chunked
    return ssd_chunked(x, dt, A, B, C, chunk=SCAN_Q, h0=h0)


def _scan_of_recurrence(x, dt, A, B, C, h0):
    """The per-position recurrence (models/ssd.ssd_decode_step), scanned."""
    from repro.models.ssd import ssd_decode_step

    def step(h, t):
        x_t, dt_t, B_t, C_t = t
        y, h = ssd_decode_step(h, x_t, dt_t, A, B_t, C_t)
        return h, y

    xs = [jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)]
    h, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), h


def _scan_fused(x, dt, A, B, C, h0):
    from repro.kernels.ssd_chunk import ssd_scan
    Bb, S, H, P = x.shape
    cum = jnp.cumsum((dt * A).reshape(Bb, S // SCAN_Q, SCAN_Q, H), axis=2)
    y, h = ssd_scan(x.reshape(Bb, S, H * P), dt, cum.reshape(Bb, S, H), B,
                    C, h0, chunk=SCAN_Q, interpret=True)
    return y.reshape(Bb, S, H, P), h


def _scan_loss(fn, w, wh):
    def loss(*a):
        y, h = fn(*a)
        return jnp.sum(y * w) + jnp.sum(h * wh)
    return loss


def _assert_close(got, want, rtol):
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=rtol * scale)


# the mixer hands x, B and C over in the model's dtype; a bf16 case
# compares with the oracles on the same bf16-rounded inputs in f32, its
# bf16 y, dy and d(x, B, C) rounded once more: about 5 unit roundoffs
# (2^-9) of the largest magnitude
TOL = {jnp.float32: (2e-5, 1e-4), jnp.bfloat16: (1e-2, 1e-2)}


def _in_dtype(args, dtype):
    """x, B and C of _scan_inputs in dtype, the rest as they are."""
    return tuple(a.astype(dtype) if i in (0, 3, 4) else a
                 for i, a in enumerate(args))


SCAN_SHAPES = [(4, 64, 1, 128), (4, 64, 2, 16), (8, 32, 2, 16),
               (2, 128, 1, 16), (20, 64, 1, 16)]


@pytest.mark.parametrize("H,P,G,N,dtype", [
    pytest.param(*shape, dtype, id="-".join(map(str, shape)) + suffix)
    for dtype, suffix in ((jnp.float32, ""), (jnp.bfloat16, "-bf16"))
    for shape in SCAN_SHAPES])
def test_ssd_scan_matches_ssd_chunked_and_recurrence(H, P, G, N, dtype):
    """y, h_last and d/d(x, dt, A, B, C, h0) of the fused scan equal the
    jnp chunked SSD and the per-position recurrence: two chunks of Q=128,
    heads of P=32/64/128 filling 128-lane tiles, groups of 2-20 heads
    (20: a group over several head steps)."""
    args = _in_dtype(_scan_inputs(1, 2 * SCAN_Q, H, P, G, N,
                                  seed=H + P + G + N), dtype)
    ref_args = tuple(a.astype(jnp.float32) for a in args)
    rtol, gtol = TOL[dtype]
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=args[0].shape), jnp.float32)
    wh = jnp.asarray(rng.normal(size=args[5].shape), jnp.float32)
    got = _scan_fused(*args)
    grads = jax.grad(_scan_loss(_scan_fused, w, wh), argnums=range(6))(
        *args)
    for oracle in (_scan_of_ssd_chunked, _scan_of_recurrence):
        for g, h in zip(got, oracle(*ref_args)):
            _assert_close(g.astype(jnp.float32), h, rtol)
        want = jax.grad(_scan_loss(oracle, w, wh), argnums=range(6))(
            *ref_args)
        for g, h in zip(grads, want):
            _assert_close(g.astype(jnp.float32), h, gtol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,P", [(8, 8), (32, 16), (64, 64), (128, 64)])
def test_ssd_scan_single_chunk_is_the_intra_chunk_term(N, P, dtype):
    """One chunk from a zero state: y is the intra-chunk term alone,
    ((C B^T) * L) @ (x dt), which ref.ssd_intra_chunk_ref computes per
    head; H = 128/P heads of one group fill one lane tile."""
    H = 128 // P
    x, dt, A, B, C, _ = _in_dtype(_scan_inputs(1, SCAN_Q, H, P, 1, N,
                                               seed=N + P), dtype)
    h0 = jnp.zeros((1, 1, H, P, N), jnp.float32)
    y, _ = _scan_fused(x, dt, A, B, C, h0)
    f32 = lambda a: a.astype(jnp.float32)
    cum = jnp.cumsum(dt * A, axis=1)[0].T                       # (H, Q)
    xdt = jnp.moveaxis(f32(x)[0] * dt[0][..., None], 1, 0)      # (H, Q, P)
    Cg, Bg = (jnp.broadcast_to(f32(a)[0, :, 0], (H, SCAN_Q, N))
              for a in (C, B))
    want = ref.ssd_intra_chunk_ref(cum, Cg, Bg, xdt)
    _assert_close(jnp.moveaxis(f32(y)[0], 1, 0), want, TOL[dtype][0])


@pytest.mark.parametrize("transform", ["vmap", "checkpoint"])
def test_ssd_scan_under_vmap_and_checkpoint(transform):
    """The client axis (vmap) and the layer remat (checkpoint) leave the
    op's values and gradients those of the jnp chunked SSD."""
    clients = [_scan_inputs(1, 2 * SCAN_Q, 4, 64, 2, 16, seed=s)
               for s in (3, 4)]
    args = [jnp.stack(a) for a in zip(*clients)]
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=args[0].shape), jnp.float32)
    if transform == "vmap":
        wrap = jax.vmap
    else:
        wrap = lambda f: jax.vmap(jax.checkpoint(f))

    def loss(fn):
        # squared, so the backward pass needs the (rematted) forward
        def f(*a):
            y, h = wrap(fn)(*a)
            return jnp.sum(jnp.square(y) * w) + jnp.sum(jnp.square(h))
        return f

    for g, h in zip(wrap(_scan_fused)(*args),
                    wrap(_scan_of_ssd_chunked)(*args)):
        _assert_close(g, h, 2e-5)
    got = jax.grad(loss(_scan_fused), argnums=range(6))(*args)
    want = jax.grad(loss(_scan_of_ssd_chunked), argnums=range(6))(*args)
    for g, h in zip(got, want):
        _assert_close(g, h, 1e-4)
