"""SSD (Mamba2) chunked algorithm vs the naive per-step recurrence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.ssd import segsum, ssd_chunked, ssd_decode_step


def naive_ssd(x, dt, A, B, C):
    """Direct recurrence: h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t."""
    Bb, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    hg = H // G
    h = np.zeros((Bb, G, hg, P, N))
    ys = []
    for t in range(S):
        y, h = ssd_decode_step(jnp.asarray(h), jnp.asarray(x[:, t]),
                               jnp.asarray(dt[:, t]), jnp.asarray(A),
                               jnp.asarray(B[:, t]), jnp.asarray(C[:, t]))
        h = np.asarray(h)
        ys.append(np.asarray(y))
    return np.stack(ys, axis=1), h


@settings(max_examples=12, deadline=None)
@given(S=st.sampled_from([8, 16, 32]), chunk=st.sampled_from([4, 8, 16]),
       seed=st.integers(0, 1000))
def test_chunked_matches_recurrence(S, chunk, seed):
    rng = np.random.default_rng(seed)
    Bb, H, P, G, N = 2, 4, 8, 2, 4
    x = rng.normal(size=(Bb, S, H, P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.2, size=(Bb, S, H)).astype(np.float32)
    A = -rng.uniform(0.5, 2.0, size=(H,)).astype(np.float32)
    B = rng.normal(size=(Bb, S, G, N)).astype(np.float32)
    C = rng.normal(size=(Bb, S, G, N)).astype(np.float32)
    y_chunk, h_chunk = ssd_chunked(jnp.asarray(x), jnp.asarray(dt),
                                   jnp.asarray(A), jnp.asarray(B),
                                   jnp.asarray(C), chunk)
    y_naive, h_naive = naive_ssd(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y_chunk), y_naive,
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(h_chunk), h_naive,
                               rtol=1e-3, atol=1e-3)


def test_initial_state_carries():
    rng = np.random.default_rng(0)
    Bb, S, H, P, G, N = 1, 16, 2, 4, 1, 4
    x = rng.normal(size=(Bb, S, H, P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.2, size=(Bb, S, H)).astype(np.float32)
    A = -rng.uniform(0.5, 2.0, size=(H,)).astype(np.float32)
    B = rng.normal(size=(Bb, S, G, N)).astype(np.float32)
    C = rng.normal(size=(Bb, S, G, N)).astype(np.float32)
    # full pass vs two half passes with carried state
    y_full, h_full = ssd_chunked(jnp.asarray(x), jnp.asarray(dt),
                                 jnp.asarray(A), jnp.asarray(B),
                                 jnp.asarray(C), 8)
    y1, h1 = ssd_chunked(jnp.asarray(x[:, :8]), jnp.asarray(dt[:, :8]),
                         jnp.asarray(A), jnp.asarray(B[:, :8]),
                         jnp.asarray(C[:, :8]), 8)
    y2, h2 = ssd_chunked(jnp.asarray(x[:, 8:]), jnp.asarray(dt[:, 8:]),
                         jnp.asarray(A), jnp.asarray(B[:, 8:]),
                         jnp.asarray(C[:, 8:]), 8, h0=h1)
    np.testing.assert_allclose(np.asarray(y_full[:, 8:]), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(h2),
                               rtol=1e-4, atol=1e-4)


def test_segsum_lower_triangular():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5,)),
                    jnp.float32)
    M = np.asarray(segsum(x))
    assert np.all(np.isneginf(M[np.triu_indices(5, 1)]))
    np.testing.assert_allclose(np.diag(M), 0.0, atol=1e-6)
    np.testing.assert_allclose(M[3, 1], float(x[2] + x[3]), rtol=1e-5)


def _fits_everywhere(monkeypatch):
    """ssd_chunked's fused path taken off the chip: scan_fits says yes and
    the kernels run in Pallas interpret mode."""
    from repro.kernels import ssd_chunk
    monkeypatch.setattr(ssd_chunk, "scan_fits", lambda *a: True)


@pytest.mark.parametrize("with_h0", [False, True])
def test_fused_scan_path_matches_jnp_path(monkeypatch, with_h0):
    """ssd_chunked on the fused scan (kernel=True) gives the jnp path's y,
    h_last and gradients at a shape that tiles on the chip (Q=128, two
    chunks, 2 groups of 2 heads of P=64)."""
    _fused_matches_jnp(monkeypatch, with_h0, (2, 256, 4, 64, 2, 16))


def test_fused_scan_eight_groups_matches_jnp_path(monkeypatch):
    """The same at Nemotron-H's grouping: 8 groups of B and C, each
    shared by 2 heads of P=64, chunks of Q=128."""
    _fused_matches_jnp(monkeypatch, True, (1, 256, 16, 64, 8, 16))


def _fused_matches_jnp(monkeypatch, with_h0, shape):
    rng = np.random.default_rng(5)
    Bb, S, H, P, G, N = shape
    x = jnp.asarray(rng.normal(size=(Bb, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (Bb, S, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(Bb, S, G, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(Bb, S, G, N)), jnp.float32)
    h0 = (jnp.asarray(rng.normal(size=(Bb, G, H // G, P, N)), jnp.float32)
          if with_h0 else None)
    w = jnp.asarray(rng.normal(size=(Bb, S, H, P)), jnp.float32)
    _fits_everywhere(monkeypatch)

    def run(kernel):
        def f(*a):
            y, h = ssd_chunked(*a, chunk=128, h0=h0, kernel=kernel)
            return y, h, jnp.sum(y * w) + jnp.sum(jnp.square(h))
        out = f(x, dt, A, B, C)
        grads = jax.grad(lambda *a: f(*a)[2], argnums=range(5))(
            x, dt, A, B, C)
        return out, grads

    for g, h in zip(jax.tree.leaves(run(True)), jax.tree.leaves(run(False))):
        scale = float(jnp.max(jnp.abs(h)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(h),
                                   rtol=1e-4, atol=1e-4 * scale)


def test_fused_scan_predicate(monkeypatch):
    """The fused scan is taken only where the config asks for it and
    Mosaic compiles (a TPU backend), with a chunk of a multiple of 128 and
    each group's heads filling 128-lane tiles; mamba2-130m at S=2048
    (Q=256, 24 heads of P=64, N=128) qualifies."""
    import dataclasses

    from repro.configs import get_config
    from repro.kernels import ssd_chunk
    from repro.models.ssd import scan_fused
    cfg = dataclasses.replace(get_config("mamba2-130m"), ssm_kernel=True)
    assert not ssd_chunk.scan_fits(256, 24, 64, 1)         # CPU backend
    assert not scan_fused(cfg, 2048)
    monkeypatch.setattr(ssd_chunk, "resolve_interpret",
                        lambda interpret: False)           # "on a TPU"
    assert ssd_chunk.scan_fits(256, 24, 64, 1)
    assert scan_fused(cfg, 2048)                           # Q = 256
    assert ssd_chunk.scan_fits(128, 8, 32, 2)              # 4 heads a tile
    assert not ssd_chunk.scan_fits(64, 24, 64, 1)         # Q < 128
    assert not ssd_chunk.scan_fits(256, 24, 48, 1)        # P ∤ 128
    assert not ssd_chunk.scan_fits(256, 3, 64, 1)         # half a tile
    assert not scan_fused(cfg, 96)                         # Q = 96
    assert not scan_fused(cfg.reduced(), 2048)             # chunk 32
    assert not scan_fused(dataclasses.replace(cfg, ssm_kernel=False), 2048)


def test_model_loss_on_fused_scan_matches_jnp(monkeypatch):
    """The whole reduced mamba2 (2 layers, 16 heads of P=32) with its SSD
    on the fused scan: the train loss and every parameter's gradient are
    the jnp path's (the mixer's D skip and layouts included)."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import transformer
    from repro.models.params import init_params
    cfg = dataclasses.replace(get_config("mamba2-130m").reduced(),
                              ssm_chunk=128, dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    t = jax.random.randint(jax.random.PRNGKey(1), (1, 257), 0, cfg.vocab)
    batch = {"tokens": t[:, :-1], "labels": t[:, 1:]}
    _fits_everywhere(monkeypatch)

    def run(kernel):
        c = dataclasses.replace(cfg, ssm_kernel=kernel)
        return jax.value_and_grad(transformer.train_loss)(params, c, batch)

    for g, h in zip(jax.tree.leaves(run(True)), jax.tree.leaves(run(False))):
        scale = float(jnp.max(jnp.abs(h)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(h),
                                   rtol=1e-4, atol=1e-4 * scale)


def test_group_rmsnorm_one_group_is_rmsnorm_and_groups_normalise_apart():
    """The gated norm by groups: one group is today's rmsnorm exactly
    (mamba2-130m's mixer); with 8 groups each slice of the channels is
    normalised on its own, gradients included."""
    from repro.models.common import group_rmsnorm, rmsnorm
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 5, 64)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(64,)) * 0.1, jnp.float32)
    np.testing.assert_array_equal(np.asarray(group_rmsnorm(x, s, 1)),
                                  np.asarray(rmsnorm(x, s)))

    def plain(x, s):
        xg = x.reshape(2, 5, 8, 8)
        var = jnp.mean(jnp.square(xg), axis=-1, keepdims=True)
        return (xg * jax.lax.rsqrt(var + 1e-5)
                * (1.0 + s.reshape(8, 8))).reshape(x.shape)

    w = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    got = jax.value_and_grad(lambda x, s: jnp.sum(
        group_rmsnorm(x, s, 8) * w), argnums=(0, 1))(x, s)
    want = jax.value_and_grad(lambda x, s: jnp.sum(plain(x, s) * w),
                              argnums=(0, 1))(x, s)
    for g, h in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(h),
                                   rtol=1e-5, atol=1e-5)
