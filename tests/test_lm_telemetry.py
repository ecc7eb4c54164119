"""The LM round's own names and counts: the device scopes of the model's
phases (``lm.embed``, ``lm.mixer``, ``lm.ssd``, ``lm.head``) nested in the
round's ``fed.local_sgd``, the applied-steps counter and the tokens-per-
step gauge, and a round left bit-identical by telemetry."""
import math
import re

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.participation import TRACES
from repro.fed import Client, LMTask, StreamScheduler
from repro.obs import Telemetry

from test_stream import make_clients, make_scheduler

SEQ = 16
B = 2
LM_SCOPES = ("lm.embed", "lm.mixer", "lm.ssd", "lm.head")


def _task():
    return LMTask(get_config("mamba2-130m").reduced(), seq_len=SEQ)


def _probe(task):
    t = task.token_stream(np.random.default_rng(9), n=B, domain=0)
    return task.make_batch({"tokens": t})


def lm_scheduler(telemetry=None):
    task = _task()
    rng = np.random.default_rng(0)
    clients = [Client(x=task.token_stream(rng, n=4, domain=d),
                      trace=TRACES[d + 5]) for d in range(3)]
    probe = _probe(task)
    loss = jax.jit(task.loss_fn)
    return StreamScheduler(
        clients=clients, task=task,
        init_params=task.init_params(jax.random.PRNGKey(0)),
        local_epochs=2, batch_size=B, scheme="C", eta0=0.05, seed=0,
        chunk_size=2, agg="tree", telemetry=telemetry,
        evaluate=lambda p: (float(loss(p, probe)), math.nan))


def test_lm_phases_are_named_scopes_inside_local_sgd():
    sch = lm_scheduler()
    eng = sch.engine
    a = sch.state.span_args(0)
    args = (sch.params, eng.data, eng.n, eng.s_cdf, sch.state.key,
            np.asarray(a["active"], np.float32), np.int32(0),
            np.asarray(a["p"], np.float32),
            np.asarray(a["reboot_tau0"], np.int32),
            np.asarray(a["reboot_boost"], np.float32), np.int32(0))
    hlo = eng._get_fn(2, sampled=True).lower(*args).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in LM_SCOPES:
        scoped = re.escape(scope) + r"(?![\w.])"
        assert any(re.search(r"fed\.local_sgd\)?/.*" + scoped, n)
                   for n in names), scope
        # the backward pass carries the scope too
        assert any(re.search(r"fed\.local_sgd\)?/.*transpose\(.*"
                             + scoped, n) for n in names), scope
    # lm.ssd is nested in lm.mixer
    assert any(re.search(r"lm\.mixer/.*lm\.ssd", n) for n in names)


def test_applied_steps_counter_and_tokens_gauge():
    tel = Telemetry()
    sch = lm_scheduler(telemetry=tel)
    sch.run(3, eval_every=10 ** 9)
    sch.run(2, eval_every=10 ** 9)
    steps = tel.registry.counter("fed_local_steps_applied_total").value
    want = float(sum(np.sum(h.s) for h in sch.history))
    assert want > 0 and steps == want
    assert tel.registry.gauge("engine_tokens_per_step").value == B * SEQ

    mlp = Telemetry()
    make_scheduler(make_clients(3, seed=0), seed=0, telemetry=mlp)
    assert mlp.registry.gauge("engine_tokens_per_step").value == 0


def test_ssd_fused_layers_gauge(monkeypatch):
    """engine_ssd_fused_layers counts the SSD layers on the fused scan, by
    the predicate ssd_chunked uses on the engine's placed task: none on
    the CPU and none without SSD layers; on a TPU every layer of a round
    placed on one device where the chunk tiles, none of a sharded one."""
    from repro.kernels import ssd_chunk

    tel = Telemetry()
    sch = lm_scheduler(telemetry=tel)
    assert tel.registry.gauge("engine_ssd_fused_layers").value == 0
    assert not sch.engine.task.cfg.ssm_kernel   # no path changed: unplaced
    mlp = Telemetry()
    make_scheduler(make_clients(3, seed=0), seed=0, telemetry=mlp)
    assert mlp.registry.gauge("engine_ssd_fused_layers").value == 0

    monkeypatch.setattr(ssd_chunk, "resolve_interpret", lambda i: False)
    full = LMTask(get_config("mamba2-130m"), seq_len=2048)
    assert full.ssd_fused_layers == 0                  # not placed yet
    assert full.placed(1).ssd_fused_layers == 24
    assert full.placed(1).cfg.ssm_kernel and not full.cfg.ssm_kernel
    assert full.placed(4) is full                      # sharded: jnp
    assert _task().placed(1).ssd_fused_layers == 0     # Q = 16 < 128


def test_lm_round_is_bit_identical_with_telemetry():
    runs = []
    for tel in (None, Telemetry()):
        sch = lm_scheduler(telemetry=tel)
        sch.run(2, eval_every=1)
        runs.append(sch)
    a, b = runs
    assert [h.loss for h in a.history] == [h.loss for h in b.history]
    assert all(np.isfinite(h.loss) for h in a.history)
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
