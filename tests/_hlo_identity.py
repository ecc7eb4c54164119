"""Fingerprint what the benchmark cells compile.

Compiles, for a described TPU v5e (one device of a v5e:2x2 topology; no
chip needed), (i) ``mnist-static``'s round chunk as ``bench/`` builds it
(100 clients, f32 wire, the config's ``chunk_size`` rounds, donated
params as on the chip), (ii) ``aggregate_deltas_flat`` over
mamba2-130m's parameter shapes at K=4 on the f32 wire and (iii)
``mamba2-fed``'s whole round as ``bench/`` builds it, its SSD on the
fused scan as a one-chip placement takes it.  Each optimized
HLO module is written to OUTDIR with its metadata, stack-frame tables
and source locations stripped (a Mosaic kernel body is reduced to the
hash of its MLIR without debug info), and its sha256 is printed.

A change meant to leave the cells' compiled programs alone prints the
same hashes as its parent:

  JAX_PLATFORMS=cpu python tests/_hlo_identity.py <tree> <outdir>

with <tree> a checkout (its ``src/`` and ``bench/`` are imported).
"""
import base64
import hashlib
import os
import re
import sys

ROOT, OUT = (os.path.abspath(a) for a in sys.argv[1:3])
os.environ["TPU_LOG_DIR"] = "disabled"
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax._src.lib.mlir import ir  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import repro.kernels.ssd_chunk as ssd_chunk  # noqa: E402
import repro.kernels.weighted_agg as wa  # noqa: E402
from bench.lib import harness  # noqa: E402
from bench.lib.fedref import Policy  # noqa: E402
from repro.core.aggregation import aggregate_deltas_flat  # noqa: E402

# an entry compiled for a described chip cannot be read back from a cache
jax.config.update("jax_enable_compilation_cache", False)
wa.resolve_interpret = lambda interpret: False      # Mosaic, as on a TPU
ssd_chunk.resolve_interpret = wa.resolve_interpret
DEV = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])


def sds(x):
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=DEV)


def _kernel_body(match):
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        asm = ir.Module.parse(base64.b64decode(match.group(1))).operation \
            .get_asm(enable_debug_info=False)
    return '"body":"sha256:%s"' % hashlib.sha256(asm.encode()).hexdigest()


def strip(text: str) -> str:
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n.*?\n\n", "", text, flags=re.S | re.M)
    return re.sub(r'"body":"([^"]*)"', _kernel_body, text)


def dump(name: str, compiled) -> None:
    text = strip(compiled.as_text())
    with open(os.path.join(OUT, name + ".hlo"), "w") as f:
        f.write(text)
    print(name, hashlib.sha256(text.encode()).hexdigest(), flush=True)


class _Captured(Exception):
    pass


def round_chunk(workload: str):
    cell = harness.Cell(harness.manifest(ROOT), workload, 1, {})
    prog = cell.driver.program(cell, cell.weights(Policy()))
    eng = prog.engine
    eng.donate = True                     # the chip's default
    build, got = eng._get_fn, {}

    def capture(R, sampled):
        def fn(*args):
            got["args"] = args
            raise _Captured
        return fn

    eng._get_fn = capture                 # the first call's arguments
    try:
        prog.run(cell.R, eval_every=1 << 30)
    except _Captured:
        pass
    prog.close()
    R = min(cell.R, eng.chunk_size)
    return build(R, True).lower(*jax.tree.map(sds, got["args"])).compile()


def mamba2_aggregation(K: int = 4):
    man = harness.manifest(ROOT)
    fam = harness.load_module(os.path.join(ROOT, "bench", "families",
                                           "mamba2.py"))
    task = fam.program_kwargs(harness.config_of(man, "mamba2-130m-fed"))[
        "task"]
    shapes = jax.eval_shape(task.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(sds, shapes)
    deltas = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        (K,) + s.shape, jnp.float32, sharding=DEV), shapes)
    coeffs = jax.ShapeDtypeStruct((K,), jnp.float32, sharding=DEV)
    return jax.jit(aggregate_deltas_flat).lower(params, deltas,
                                                coeffs).compile()


if __name__ == "__main__":
    os.makedirs(OUT, exist_ok=True)
    dump("mnist_chunk", round_chunk("mnist-static"))
    dump("mamba2_agg", mamba2_aggregation())
    dump("mamba2_round", round_chunk("mamba2-fed"))
