"""Device time by the round's named scopes and idle time by the program's
spans (bench/lib/scopes.py), on hand-built events and a CPU profile."""
import os
import subprocess
import sys

import pytest
from pytest import approx

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib import scopes  # noqa: E402
from bench.lib.scopes import Op, ScopedTrace  # noqa: E402
from bench.lib.trace import Event  # noqa: E402


@pytest.mark.parametrize("op_name, scope", [
    ("jit(chunk)/while/body/closed_call/fed.gather/vmap(jit(_take))/gather",
     "fed.gather"),
    ("jit(chunk)/while/body/vmap(fed.local_sgd)/while/body/transpose(jvp())"
     "/dot_general", "fed.local_sgd"),
    ("jit(chunk)/while/body/fed.aggregate/jit(weighted_agg)/fed.flatten"
     "/jit(_pad)/pad", "fed.flatten"),
    ("jit(chunk)/while/body/fed.aggregate/jit(weighted_agg)/weighted_agg.pad"
     "/jit(_pad)/pad", "fed.flatten"),
    ("jit(chunk)/while", ""),
    ("fed.sampler/fed.flattened", ""),
    ('%fusion.3 = f32[8] fusion(%p), metadata={op_name="jit(f)/fed.sample/'
     'lt"}', "fed.sample"),
])
def test_scope_of_takes_the_innermost_fed_scope(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _field(number: int, value) -> bytes:
    """One protobuf field: an int as a varint, bytes or str as a
    length-delimited value."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key: int, value: bytes) -> bytes:
    return _field(1, key) + _field(2, value)


def _hlo(*instrs) -> bytes:
    """A one-computation HloProto (field numbers of xla's hlo.proto);
    instrs: (id, name, opcode, op_name, operand ids)."""
    body = b""
    for id_, name, opcode, op_name, operands in instrs:
        i = _field(1, name) + _field(2, opcode) + _field(35, id_)
        if op_name:
            i += _field(7, _field(2, op_name))
        i += b"".join(_field(36, o) for o in operands)
        body += _field(2, i)
    return _field(1, _field(3, body))


PROG = 2**63 + 5          # a program id past int64, as the profiler keeps


def _space(hlo: bytes) -> bytes:
    """A hand-encoded XSpace (field numbers of tsl's xplane.proto): a
    device plane whose ops carry tf_op and program_id stats in their event
    metadata, a host plane, and the metadata plane's HLO module."""
    stat_md = (_field(5, _entry(7, _field(1, 7) + _field(2, "tf_op")))
               + _field(5, _entry(8, _field(1, 8) + _field(2, "flops")))
               + _field(5, _entry(9, _field(1, 9) + _field(2, "program_id"))))
    prog = _field(5, _field(1, 9) + _field(3, PROG))

    def event(key, name, *stats):
        return _field(4, _entry(key, _field(1, key) + _field(2, name)
                                + b"".join(stats)))

    fusion = event(3, "%fusion.1 = f32[2] fusion(%p)",
                   _field(4, "fusion.1"),
                   _field(5, _field(1, 8) + _field(4, 16)),
                   _field(5, _field(1, 7)
                          + _field(5, "jit(f)/fed.gather/take")),
                   prog)
    copy = event(4, "%copy.2 = f32[2] copy(%fusion.1)",
                 _field(5, _field(1, 8) + _field(6, b"\x00\xff")))
    dus = event(5, "%dus.3 = f32[4] dynamic-update-slice(%fusion.1)", prog)
    device = (_field(1, 1) + _field(2, "/device:TPU:0")
              + _field(3, _field(2, "XLA Ops"))          # a line: skipped
              + fusion + copy + dus + stat_md)
    host = _field(2, "/host:CPU") + stat_md + fusion
    meta = (_field(2, "/host:metadata")
            + _field(5, _entry(1, _field(1, 1) + _field(2, "Hlo Proto")))
            + _field(4, _entry(PROG, _field(2, "jit_f")
                               + _field(5, _field(1, 1) + _field(6, hlo)))))
    return _field(1, device) + _field(1, host) + _field(1, meta)


def test_op_names_reads_tf_op_from_device_event_metadata(tmp_path):
    """A device op's scope comes from the tf_op stat of its event
    metadata; one without it (a compiler-made op) from its module's HLO,
    found by its program_id stat; an op with neither is left out, as are
    other stats and host planes."""
    hlo = _hlo((1, "fusion.1", "fusion", "jit(f)/fed.gather/take", ()),
               (2, "dus.3", "dynamic-update-slice", "", (1,)),
               (3, "concatenate.4", "concatenate",
                "jit(f)/fed.flatten/concatenate", (2,)))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_space(hlo))
    assert scopes.op_scopes(str(path)) == {
        "%fusion.1 = f32[2] fusion(%p)": "fed.gather",
        "%dus.3 = f32[4] dynamic-update-slice(%fusion.1)": "fed.flatten"}


@pytest.mark.parametrize("instrs, want", [
    # the pieces of a concatenate take the concatenate's scope, not that
    # of the local-SGD fusion that feeds the first piece
    ([(1, "sub.1", "fusion", "j/fed.local_sgd/sub", ()),
      (2, "dus.2", "dynamic-update-slice", "", (1,)),
      (3, "dus.3", "dynamic-update-slice", "", (2,)),
      (4, "dus.4", "dynamic-update-slice", "j/fed.flatten/concatenate", (3,))],
     {"dus.2": "fed.flatten", "dus.3": "fed.flatten"}),
    # no scoped user (only the loop's tuple): the nearest producer's scope
    ([(1, "add.1", "add", "j/fed.aggregate/add", ()),
      (2, "copy.2", "copy", "", (1,)),
      (3, "tuple.3", "tuple", "", (2,)),
      (4, "copy.4", "copy", "", (3,)),
      (5, "mul.5", "multiply", "j/fed.local_sgd/mul", (4,))],
     {"copy.2": "fed.aggregate", "copy.4": "fed.local_sgd", "tuple.3": ""}),
    # a shared constant joins nothing; an op_name outside every phase
    # stays unscoped
    ([(1, "c.1", "constant", "", ()),
      (2, "pad.2", "pad", "", (1,)),
      (3, "mul.3", "multiply", "j/fed.sample/mul", (1,)),
      (4, "while.4", "while", "j/while", (3,))],
     {"pad.2": "", "c.1": "", "while.4": ""}),
], ids=["concatenate", "producer", "barriers"])
def test_compiler_made_ops_take_their_users_scope(instrs, want):
    got = scopes._module_scopes(_hlo(*instrs))
    assert {k: got[k] for k in want} == want


def _trace():
    """One chip, one window [0, 1000]: a round's while loop holding a
    scoped body, a kernel, unscoped compiler copies, and idle gaps inside
    and outside the program's spans."""
    ops = [Op("%while.5 = (...) while(...)", 0, 400, ""),
           Op("%fusion.1 = f32[8] fusion(...)", 10, 100, "fed.gather"),
           Op("%while.7 = (...) while(...)", 120, 200, "fed.local_sgd"),
           Op("%fusion.2 = f32[8] fusion(...)", 130, 150, "fed.local_sgd"),
           Op("%copy.4 = f32[8] copy(...)", 330, 40, ""),
           Op("%weighted_agg.7 = f32[1,128] custom-call(...)", 500, 100,
              "fed.aggregate"),
           Op("%pad.3 = f32[8] pad(...)", 650, 50, "fed.flatten"),
           Op("%fusion.9 = f32[8] fusion(...)", 900, 50, "fed.sample")]
    host = [Event("bench.window", 0, 1000), Event("bench.call", 0, 980)]
    spans = [Event("sched.run", 0, 880),
             Event("engine.dispatch", 380, 150),
             Event("sched.flush", 720, 150)]
    return ScopedTrace(devices={"/device:TPU:0": ops}, host=host,
                       spans=spans)


def test_scope_self_time_counts_a_loop_once():
    t = _trace()
    got = scopes.scope_seconds(t, 0, 1000)
    # the outer loop's self time: 400 - 100 - 200 - 40 = 60 (unscoped);
    # the local-SGD loop: 200 - 150 = 50 of its own, plus its body
    assert got == approx({"": (60 + 40) * 1e-9, "fed.gather": 100e-9,
                          "fed.local_sgd": 200e-9,
                          "fed.aggregate": 100e-9, "fed.flatten": 50e-9,
                          "fed.sample": 50e-9})
    assert sum(got.values()) == approx(600e-9)     # = the busy union
    assert scopes.unscoped_ops(t, 0, 1000) == [
        ["%while.5", approx(60e-9)], ["%copy.4", approx(40e-9)]]
    # ops that start outside the window are left out
    assert scopes.scope_seconds(t, 600, 1000) == approx(
        {"fed.flatten": 50e-9, "fed.sample": 50e-9})


def test_idle_is_attributed_to_the_innermost_program_span():
    t = _trace()
    # gaps: [400,500] mid 450 in engine.dispatch; [600,650] mid 625 in
    # sched.run; [700,900] mid 800 in sched.flush; [950,1000] mid 975
    # outside every program span: not counted
    got = scopes.span_idle_seconds(t, 0, 1000)
    assert got == approx({"engine.dispatch": 100e-9, "sched.run": 50e-9,
                          "sched.flush": 200e-9})


def test_idle_without_program_spans_counts_nothing():
    t = _trace()
    t.spans = []
    assert scopes.span_idle_seconds(t, 0, 1000) == {}


def test_report_per_round_and_share_of_busy():
    t = _trace()
    rep = scopes.report(t, 0, 1000, rounds=2)
    ms = 1e3 / 2
    assert rep["busy_ms_per_round"] == approx(600e-9 * ms)
    assert rep["scope_ms_per_round"] == approx(
        {"fed.sample": 50e-9 * ms, "fed.gather": 100e-9 * ms,
         "fed.local_sgd": 200e-9 * ms, "fed.flatten": 50e-9 * ms,
         "fed.aggregate": 100e-9 * ms})
    assert rep["unscoped_ms_per_round"] == approx(100e-9 * ms)
    assert rep["scoped_share_of_busy"] == approx(500 / 600)
    assert rep["program_idle_ms_per_round"] == approx(350e-9 * ms)
    assert list(rep["idle_ms_per_round_by_span"]) == [
        "engine.dispatch", "sched.flush", "sched.run"]


def test_load_keeps_program_spans_from_a_profile(tmp_path):
    """On the CPU a profile has no device plane, but its host plane holds
    the harness's annotations and the program's spans."""
    code = f"""
import sys
sys.path[:0] = [{os.path.join(ROOT, "src")!r}, {ROOT!r}]
import jax, jax.numpy as jnp
from bench.lib import scopes
from repro.obs import resolve
jax.profiler.start_trace({str(tmp_path)!r})
with jax.profiler.TraceAnnotation("bench.window"):
    with resolve(None).span("sched.flush", spans=1):
        jnp.ones(4).block_until_ready()
jax.profiler.stop_trace()
t = scopes.load({str(tmp_path)!r})
print(sorted(e.name for e in t.host), sorted(e.name for e in t.spans),
      t.devices)
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "['bench.window'] ['sched.flush'] {}"


def test_profile_tool_refuses_without_a_chip():
    r = subprocess.run([sys.executable, "bench/profile_scopes.py",
                        "--workload", "mnist-static", "--seed", "7"],
                       cwd=ROOT, capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 2
    assert r.stdout.strip() == ""
