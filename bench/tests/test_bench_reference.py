"""The plain references compute the same loss and gradient as the
program's models, at a small size on the CPU in float32."""
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib import harness  # noqa: E402
from bench.lib.fedref import Policy  # noqa: E402

SMALL = {
    "mnist-mlp-paper": {"model": {"hidden": [16, 16]}},
    "mamba2-130m": {"model": {"n_layer": 2, "d_model": 64, "d_state": 16,
                              "headdim": 16, "vocab_size": 250,
                              "vocab_padded": 256, "chunk_size": 16,
                              "dtype": "float32"},
                    "training": {"seq": 64}},
}


@pytest.mark.parametrize("config", sorted(SMALL))
def test_reference_loss_and_grad_match_program(config):
    cfg = harness.merge(harness.load_json(os.path.join(
        ROOT, "bench", "configs", f"{config}.json")), SMALL[config])
    fam = harness.load_module(os.path.join(ROOT, "bench", "families",
                                           f"{cfg['family']}.py"))
    ref = harness.load_module(os.path.join(ROOT, "bench", "configs",
                                           f"{cfg['family']}_ref.py"))
    kw = fam.program_kwargs(cfg)
    prog_loss = kw["task"].loss_fn if "task" in kw else kw["loss_fn"]
    params = ref.init(jax.random.PRNGKey(3), cfg, Policy())
    rng = np.random.default_rng(0)
    if cfg["family"] == "mlp":
        batch = {"x": rng.normal(size=(10, 28, 28)).astype(np.float32),
                 "y": rng.integers(0, 10, size=10).astype(np.int32)}
    else:
        toks = rng.integers(0, cfg["model"]["vocab_size"],
                            size=(2, cfg["training"]["seq"] + 1))
        batch = ref.make_batch({"tokens": toks.astype(np.int32)})
    want_l, want_g = jax.value_and_grad(ref.make_loss(cfg, Policy()))(
        params, batch)
    got_l, got_g = jax.value_and_grad(prog_loss)(params, batch)
    np.testing.assert_allclose(got_l, want_l, rtol=2e-5)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        scale = float(np.max(np.abs(b))) or 1.0
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=2e-4)
