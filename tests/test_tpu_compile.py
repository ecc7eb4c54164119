"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode accepts block shapes that the chip's compiler refuses, so
these tests compile each kernel with ``interpret=False`` for a v5e:2x2
topology that is described here, not attached, at the widths the engine
runs: MNIST_MLP's flat width (199,210) and mamba2-130m's (from
``jax.eval_shape`` of ``LMTask.init_params``).  Nothing runs; a case
passes when the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture (only the
worker given this file loads the TPU library), and the persistent
compilation cache is off while it is in use: an entry compiled for a
described chip cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import ssd_chunk, weighted_agg as wa

CHUNK = 256


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topology):
    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(scope="module")
def four_chips(topology):
    return Mesh(np.array(topology.devices).reshape(4), ("data",))


@pytest.fixture(scope="module")
def widths():
    from repro.configs import get_config
    from repro.configs.paper import MNIST_MLP
    from repro.fed import LMTask
    from repro.models.small import init_small

    def flat(init):
        shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
        return sum(x.size for x in jax.tree.leaves(shapes))

    lm = LMTask(get_config("mamba2-130m"), seq_len=128)
    return {"mnist_mlp": flat(lambda k: init_small(k, MNIST_MLP)),
            "mamba2-130m": flat(lm.init_params)}


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_widths_are_the_published_ones(widths):
    assert widths["mnist_mlp"] == 199_210
    assert widths["mamba2-130m"] > 100_000_000


# K=100 slots (the paper's 100 devices) takes the K-tiled grid.  At
# mamba2-130m's width a (100, D) delta matrix alone exceeds the chip's
# 16 GB, which the compiler reports, so the wide cases keep K small.
# int8 cases are the quantized wire: codes of a whole number of chunks
# plus their f32 scales.
@pytest.mark.parametrize("model,K,dtype", [
    ("mnist_mlp", 6, jnp.float32), ("mnist_mlp", 20, jnp.bfloat16),
    ("mnist_mlp", 100, jnp.float32), ("mamba2-130m", 6, jnp.float32),
    ("mamba2-130m", 20, jnp.bfloat16), ("mnist_mlp", 6, jnp.int8),
    ("mnist_mlp", 100, jnp.int8), ("mamba2-130m", 6, jnp.int8)])
def test_weighted_agg_compiles(one_chip, widths, model, K, dtype):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    D, kw = widths[model], {}
    operands = (sds((K, D), dtype),)
    if dtype == jnp.int8:
        n_chunks = -(-D // CHUNK)
        operands = (sds((K, n_chunks * CHUNK), jnp.int8),
                    sds((K, n_chunks), jnp.float32))
        kw = {"chunk": CHUNK}
    _compile(lambda c, *ops: wa.weighted_agg(c, *ops, interpret=False, **kw),
             sds((K,), jnp.float32), *operands)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8, "leaves"])
def test_weighted_agg_sharded_compiles(four_chips, widths, monkeypatch,
                                       dtype):
    """The client-sharded reduction over a described four-chip mesh:
    each chip's launch over its 25 of 100 clients holds the kernel, and
    a cross-chip all-reduce finishes it; "leaves" passes the paper MLP's
    f32 delta leaves, one launch each.  The sharded wrapper takes no
    ``interpret``, so the kernel is steered to Mosaic here."""
    monkeypatch.setattr(wa, "resolve_interpret",
                        lambda i: False if i is None else bool(i))
    sds = lambda shape, dt, *spec: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(four_chips, P("data", *spec)))
    K, D, kw = 100, widths["mnist_mlp"], {}
    if dtype == "leaves":
        shapes = [(784, 200), (200,), (200, 200), (200,), (200, 10), (10,)]
        operands = ([sds((K,) + s, jnp.float32) for s in shapes],)
    else:
        operands = (sds((K, D), dtype, None),)
    if dtype == jnp.int8:
        n_chunks = -(-D // CHUNK)
        operands = (sds((K, n_chunks * CHUNK), jnp.int8, None),
                    sds((K, n_chunks), jnp.float32, None))
        kw = {"chunk": CHUNK}
    text = jax.jit(lambda c, *ops: wa.weighted_agg_sharded(
        c, *ops, mesh=four_chips, **kw)).lower(
        sds((K,), jnp.float32), *operands).compile().as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text


@pytest.mark.parametrize("model,K", [("mnist_mlp", 100),
                                     ("mamba2-130m", 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weighted_agg_compiles_per_leaf(one_chip, model, K, dtype):
    """The f32 and bf16 wires' launches, one per parameter leaf where
    the leaf lies, at the cells' leaves: mamba2-130m's published ones
    (lane-sparse minor dims, a second-minor of 4) and the paper MLP's."""
    from repro.configs import get_config
    from repro.configs.paper import MNIST_MLP
    from repro.fed import LMTask
    from repro.models.small import init_small

    if model == "mnist_mlp":
        init = lambda k: init_small(k, MNIST_MLP)
    else:
        init = LMTask(get_config("mamba2-130m"), seq_len=128).init_params
    leaves = [jax.ShapeDtypeStruct((K,) + x.shape, dtype, sharding=one_chip)
              for x in jax.tree.leaves(jax.eval_shape(
                  init, jax.random.PRNGKey(0)))]
    text = jax.jit(lambda c, xs: [wa.weighted_agg(c, x, interpret=False)
                                  for x in xs]).lower(
        jax.ShapeDtypeStruct((K,), jnp.float32, sharding=one_chip),
        leaves).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == len(leaves)


def test_ssd_scan_fwd_and_bwd_compile(one_chip):
    """The fused SSD scan, forward and backward under the layer remat, at
    mamba2-fed's shapes: 8 sequences (4 clients x B=2) of 8 chunks of
    Q=256, 24 heads of P=64 in one group, N=128; x, B and C in bf16 as
    the mixer hands them over."""
    Bb, S, H, G, P, N = 8, 2048, 24, 1, 64, 128
    sds = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    f32, bf16 = jnp.float32, jnp.bfloat16

    def op(x, dt, cum, B, C, h0):
        return ssd_chunk.ssd_scan(x, dt, cum, B, C, h0, chunk=CHUNK,
                                  interpret=False)

    def loss(*a):
        y, h = jax.checkpoint(op)(*a)
        return jnp.sum(jnp.square(y.astype(f32))) + jnp.sum(h)

    args = (sds(bf16, Bb, S, H * P), sds(f32, Bb, S, H), sds(f32, Bb, S, H),
            sds(bf16, Bb, S, G, N), sds(bf16, Bb, S, G, N),
            sds(f32, Bb, G, H // G, P, N))
    _compile(op, *args)
    text = jax.jit(jax.grad(loss, argnums=range(6))).lower(
        *args).compile().as_text()
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text



def test_nemotron3_fed_round_fits_one_chip(one_chip, monkeypatch):
    """nemotron3-fed's round (bench/configs/nemotron3-nano-fed.json: 7
    layers MEMEM*E at the published widths, 8 of 128 experts, 4 clients,
    E=2, B=1, S=4096, client-sequential) compiles for one described v5e
    as the engine places it, with the 3 M layers on the fused SSD scan
    and the experts on the grouped-matmul kernel, in under 14 GB of
    temporaries (the chip's 16 GB less headroom and the arguments)."""
    import os
    import sys

    from repro.core.participation import TRACES
    from repro.fed import Client, RoundEngine
    from repro.models import moe
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from bench.lib import harness

    monkeypatch.setattr(ssd_chunk, "resolve_interpret", lambda i: False)
    monkeypatch.setattr(moe, "resolve_interpret", lambda i: False)
    cfg = harness.config_of(harness.manifest(root), "nemotron3-nano-fed")
    fam = harness.load_module(os.path.join(root, "bench", "families",
                                           "nemotron_h.py"))
    task = fam.program_kwargs(cfg)["task"]
    t, fed = cfg["training"], cfg["federation"]
    seq = np.zeros((fed["samples"], t["seq"] + 1), np.int32)
    clients = [Client(x=seq, trace=TRACES[i]) for i in range(fed["clients"])]
    eng = RoundEngine(clients=clients, task=task, batch_size=t["batch"],
                      local_epochs=t["local_steps"], scheme=t["scheme"],
                      eta0=t["eta0"], chunk_size=t["chunk_size"],
                      mode=t["engine_mode"], donate=True)
    assert eng.task.ssd_fused_layers == 3 and eng.task.cfg.moe_kernel
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    like = lambda a: sds(np.shape(a), a.dtype)
    C = eng.capacity
    params = jax.tree.map(like, jax.eval_shape(task.init_params,
                                               jax.random.PRNGKey(0)))
    args = (params, jax.tree.map(like, eng.data), like(eng.n),
            like(eng.s_cdf), like(jax.random.PRNGKey(0)),
            sds((C,), jnp.float32), sds((), jnp.int32),
            sds((C,), jnp.float32), sds((C,), jnp.int32),
            sds((C,), jnp.float32), sds((), jnp.int32))
    compiled = eng._get_fn(t["span_rounds"], sampled=True).lower(
        *args).compile()
    text = compiled.as_text()
    assert "%gmm" in text and "%tgmm" in text
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 14e9
