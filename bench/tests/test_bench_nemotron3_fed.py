"""The ``nemotron3-fed`` cell: its configuration at the published widths
and parameter count, the program against the plain reference at a small
size on the CPU (the layer pattern, the expert share, dropless dispatch),
one client-sequential round through the harness, the faults and the
control that the check must catch, and the FLOP and byte counts."""
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib import harness, hybrid_flops  # noqa: E402
from bench.lib.fedref import DOWN, Policy  # noqa: E402

CELL = "nemotron3-fed"

# the pattern MEM*E at small widths; 4 of 8 experts held, 8 groups of
# SSD heads as in the cell
SMALL = {
    "hidden_size": 64, "mamba_num_heads": 16, "mamba_head_dim": 8,
    "n_groups": 8, "ssm_state_size": 8, "chunk_size": 16,
    "n_routed_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "hybrid_override_pattern": "MEM*E", "vocab_size": 256,
    "model": {"vocab_size": 256, "router_experts": 8},
    "federation": {"clients": 2, "samples": 4},
    "training": {"seq": 64},
}
F32 = dict(SMALL, model=dict(SMALL["model"], dtype="float32"))


def _cfg(overrides=None):
    man = harness.manifest()
    cfg = harness.config_of(man, harness.cell_of(man, CELL)["config"])
    return harness.merge(cfg, overrides)


def _ref(cfg):
    return harness.load_module(os.path.join(
        ROOT, "bench", "configs", f"{cfg['family']}_ref.py"))


def _program(cfg):
    fam = harness.load_module(os.path.join(ROOT, "bench", "families",
                                           f"{cfg['family']}.py"))
    return fam.program_kwargs(cfg)["task"]


def _run(**kw):
    return harness.run_cell(CELL, 1234567891011, 0.5, False,
                            t_start=time.perf_counter(), overrides=SMALL,
                            **kw)


# -- the configuration ----------------------------------------------------------
def test_config_holds_the_published_widths_and_its_cut():
    cfg = _cfg()
    published = {
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
        "chunk_size": 128, "num_experts_per_tok": 6,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "routed_scaling_factor": 2.5, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128,
        "layer_norm_epsilon": 1e-5, "tie_word_embeddings": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h"}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers",
                              "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    pat = cfg["hybrid_override_pattern"]
    assert pat == cfg["published"]["hybrid_override_pattern"][:7]
    assert len(pat) == cfg["num_hidden_layers"] == 7
    assert cfg["n_routed_experts"] == 8
    assert cfg["model"]["router_experts"] == 128
    assert cfg["vocab_size"] == cfg["model"]["vocab_size"] == 16384
    t = cfg["training"]
    assert (t["engine_mode"], t["batch"], t["seq"], t["local_steps"]) == \
        ("client_sequential", 1, 4096, 2)


def test_model_block_has_the_published_parameter_count():
    """528,093,120: 3 M layers of 38,744,896, 3 E layers of 100,125,440
    (the router bias included), one * layer of 23,399,040, the embedding
    and the head of 44,040,192 each and the final norm; the program's
    own init has the reference's tree."""
    cfg = _cfg()
    shapes = jax.eval_shape(lambda k: _ref(cfg).init(k, cfg, Policy()),
                            jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    assert count(shapes) == cfg["model"]["n_params"] == 528_093_120
    assert count(shapes["ssm_blocks"]) == 3 * 38_744_896
    assert count(shapes["expert_blocks"]) == 3 * 100_125_440
    assert count(shapes["attn_blocks"]) == 23_399_040
    prog = jax.eval_shape(_program(cfg).init_params, jax.random.PRNGKey(0))
    assert jax.tree.structure(prog) == jax.tree.structure(shapes)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), prog) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), shapes)


def test_limits_are_set_and_finite():
    limits = _cfg()["limits"]
    assert set(limits) == {"update1_gap", "change3_gap", "loss_gap",
                           "s_mismatch"}
    assert all(math.isfinite(v) for v in limits.values())
    assert limits["s_mismatch"] == 0
    assert all(0 < limits[k] < 1 for k in ("update1_gap", "change3_gap",
                                           "loss_gap"))


# -- the program against the plain reference -------------------------------------
def _small_model(overrides=F32, seed=3):
    cfg = _cfg(overrides)
    ref = _ref(cfg)
    params = ref.init(jax.random.PRNGKey(seed), cfg, Policy())
    t = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 65), 0,
                           cfg["model"]["vocab_size"])
    return cfg, ref, params, {"tokens": t[:, :-1], "labels": t[:, 1:]}


def _close(got, want, tol=2e-4):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-6)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=tol, atol=tol * scale)


def test_pattern_matches_the_reference_logits_loss_and_gradients():
    """MEM*E at small widths in float32, 4 of 8 experts held: the
    program's logits, loss and every parameter's gradient are the
    reference's (the router bias's gradient is zero in both)."""
    from repro.models import transformer
    cfg, ref, params, batch = _small_model()
    arch = _program(cfg).cfg
    with jax.default_matmul_precision("highest"):
        h, _, _ = transformer.model_forward(params, arch, batch["tokens"])
        _close(transformer.logits_fn(params, arch, h),
               ref.logits(cfg, Policy(), params, batch["tokens"]))
        got = jax.value_and_grad(transformer.train_loss)(params, arch, batch)
        want = jax.value_and_grad(ref.make_loss(cfg, Policy()))(params,
                                                                batch)
    _close(got, want)
    bias = got[1]["expert_blocks"]["moe"]["router_bias"]
    assert not np.any(np.asarray(bias))


def _expert_layer(cfg, params, x, **share):
    """The program's expert layer 0 over x, holding the experts
    ``share`` names (offset and count), its weights sliced to them."""
    import dataclasses

    from repro.models.moe import moe_ffn
    arch = _program(cfg).cfg
    arch = dataclasses.replace(arch, moe_expert_offset=share["offset"],
                               moe_experts_held=share["held"])
    p = jax.tree.map(lambda a: a[0], params["expert_blocks"]["moe"])
    sl = slice(share["offset"], share["offset"] + share["held"])
    p["experts"] = jax.tree.map(lambda a: a[sl], p["experts"])
    if not share.get("shared", True):
        del p["shared"]
    return moe_ffn(p, x, arch)[0]


def test_expert_shares_add_up_to_the_uncut_layer():
    """Two shares of 4 of 8 experts, the shared expert counted once, add
    up to the uncut layer, which is the reference's with all 8 held."""
    cfg, ref, params, _ = _small_model(harness.merge(
        F32, {"n_routed_experts": 8}))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 64, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        a = _expert_layer(cfg, params, x, offset=0, held=4)
        b = _expert_layer(cfg, params, x, offset=4, held=4, shared=False)
        whole = _expert_layer(cfg, params, x, offset=0, held=8)
        p = jax.tree.map(lambda t: t[0], params["expert_blocks"]["moe"])
        want = ref._experts(p, x, ref.dims(cfg), Policy().mm)
    _close(a + b, whole)
    _close(a + b, want)


def test_dropless_under_imbalance():
    """A correction bias that makes every token choose held expert 0:
    all 128 of its slots go through the grouped matmul, nothing is
    dropped, and the layer is the reference's."""
    cfg, ref, params, _ = _small_model()
    moe = params["expert_blocks"]["moe"]
    moe["router_bias"] = moe["router_bias"].at[:, 0].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 64, 64), jnp.float32)
    p = jax.tree.map(lambda t: t[0], moe)
    with jax.default_matmul_precision("highest"):
        got = _expert_layer(cfg, params, x, offset=0, held=4)
        want = ref._experts(p, x, ref.dims(cfg), Policy().mm)
        top = jax.lax.top_k(jax.nn.sigmoid(x.reshape(-1, 64) @ p["router"])
                            + p["router_bias"], 2)[1]
    assert np.all(np.asarray(top)[:, 0] == 0)
    _close(got, want)


# -- one round through the harness ------------------------------------------------
def test_sound_small_run_is_correct():
    out = _run()
    assert out["correct"] is True
    assert out["checks"]["s_mismatch"]["value"] == 0


def _half_batch(monkeypatch):
    """Each local step's loss over the first half of its batch's tokens
    (at the cell's B=1, half of the batch axis would be no sequence)."""
    from repro.fed import LMTask
    whole = LMTask.make_batch

    def make_batch(self, gathered):
        b = whole(self, gathered)
        return jax.tree.map(lambda a: a[..., :a.shape[-1] // 2], b)
    monkeypatch.setattr(LMTask, "make_batch", make_batch)


def _unchanged(monkeypatch):
    """A round that leaves the parameters unchanged."""
    from repro.core import fed_step

    def local_sgd(loss_fn, params, batches, alpha, eta):
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                            params)
    monkeypatch.setattr(fed_step, "local_sgd", local_sgd)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_broken_round_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = _run()
    assert out["correct"] is False
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("variant", ["control", "half_batch"])
def test_reference_variants_fail_a_limit(variant):
    """The control (one step down in precision) fails a limit, and so
    does the reference's half_batch fault, which at the cell's B=1 keeps
    no sequence of a batch and so changes nothing (both gaps read 1).
    The router bias has no gradient and is left out of the comparison."""
    c = harness.Cell(harness.manifest(), CELL, 99, SMALL)
    ref = c.reference(Policy())
    pol = (Policy(lower=DOWN[c.cfg["model"]["dtype"]])
           if variant == "control" else Policy(fault=variant))
    snaps, s = c.reference(pol)
    got = harness.check(c, snaps, s, ref)
    assert any(got[k] > lim for k, lim in c.cfg["limits"].items())
    assert "['expert_blocks']['moe']['router_bias']" in got["_excluded"]


# -- operations and bytes -----------------------------------------------------------
def test_flops_per_token_by_hand():
    """By the shapes: matmul weights a token 3 x 38,707,200 (M) + 3 x
    (344,064 + 0.375 x 9,977,856 + 19,955,712) (E) + 23,396,352 (*) +
    44,040,192 (head) = 255,682,560; SSD 2*128*128*8 + 64*(2*128*64 +
    4*128*64) = 3,407,872 a layer; attention 2 * 32 * 128 * 4097 =
    33,562,624.  About 1.67 GFLOP a token."""
    cfg = _cfg()
    assert hybrid_flops.matmul_params_per_token(cfg) == 255_682_560
    assert hybrid_flops.ssd_flops_per_token(cfg) == 3_407_872
    assert hybrid_flops.attn_flops_per_token(cfg, 4096) == 33_562_624
    want = 6 * 255_682_560 + 3 * 3 * 3_407_872 + 3 * 33_562_624
    assert hybrid_flops.flops_per_token(cfg, 4096) == want
    assert 1.66e9 < want < 1.68e9


def test_kernel_costs_by_hand():
    """The held experts' grouped matmuls over 1,536 expected rows of a
    4,096-token step (2 projections x fwd, dx, dW x 3 layers), and the
    SSD's forward and backward over 3 layers."""
    cfg = _cfg()
    r, d, f = 1536, 2688, 1856
    flops, nbytes = hybrid_flops.moe_gmm_cost(cfg, 4096)
    assert flops == 3 * 2 * 3 * 2 * r * d * f
    assert nbytes == 3 * 2 * 3 * (8 * d * f * 2 + r * (d + f) * 2)
    flops, nbytes = hybrid_flops.ssd_scan_cost(cfg, 4096)
    assert flops == 3 * 3 * 3_407_872 * 4096
    fwd = 4096 * 2 + 64 * 8 + 2048 * 2 + 4096 * 2
    bwd = 2 * 4096 * 2 + 64 * 8 + 2048 * 2 + 4096 * 2 + 64 * 8 + 2048 * 4
    assert nbytes == 3 * (fwd + bwd) * 4096
