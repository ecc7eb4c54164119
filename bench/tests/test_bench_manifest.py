"""BENCHMARK.json against the benchmark's contract, and every file a cell
needs found by name."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib import flops, harness, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["paths"] == ["bench"]
    assert man["command"][1] == "bench/run.py"
    assert 1 <= man["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_keys(man):
    names = []
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for kind in ("end_to_end", "per_layer"):
        for m in man[kind]:
            names.append(m["name"])
            assert UNIT.match(m["unit"]), m["unit"]
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
            if kind == "end_to_end":
                assert m["source"] in ("host_clock", "device_trace")
                assert 0.01 <= m["bound"] <= 0.25
    for n in names:
        assert NAME.match(n), n
    texts = [c[k] for c in man["configs"] for k in ("why", "source")]
    texts += [w["why"] for w in man["workloads"]]
    texts += [m["layer"] for m in man["per_layer"]] + man["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    metric_names = [m["name"] for k in ("end_to_end", "per_layer")
                    for m in man[k]]
    assert len(set(metric_names)) == len(metric_names)
    assert len({w["name"] for w in man["workloads"]}) == \
        len(man["workloads"])


def test_every_cell_reports_setup_and_its_per_layer_moves(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for w in man["workloads"]:
        got = [m["name"] for m in harness.metrics_of(man, w["name"],
                                                     "end_to_end")]
        assert "setup_s" in got and len(got) >= 2, w["name"]
        assert harness.metrics_of(man, w["name"], "per_layer"), w["name"]
    for m in man["per_layer"]:
        moves = e2e[m["moves"]]
        for cell in m.get("workloads", []):
            assert cell in moves.get("workloads", [cell]), (m["name"], cell)


def test_at_most_half_the_cells_on_four_chips(man):
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(man["workloads"]) // 2)


def test_files_found_by_name(man):
    for c in man["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert c["file"].startswith("bench/")
        for part in ("families", "configs"):
            suffix = "" if part == "families" else "_ref"
            assert os.path.exists(os.path.join(
                ROOT, "bench", part, f"{cfg['family']}{suffix}.py"))
        assert set(c["reduced"]) == set(cfg["reduced"])
    for w in man["workloads"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           f"{w['traffic']}.json"))
        assert w["config"] in {c["name"] for c in man["configs"]}
        mix = traffic.load(w["traffic"])
        assert os.path.exists(traffic.driver_path(mix)), w["traffic"]
    for kind in ("end_to_end", "per_layer"):
        for m in man[kind]:
            assert os.path.exists(harness.reader_path(m["name"]))


def test_config_sizes_agree_with_their_parameter_counts(man):
    def model(name):
        return harness.load_json(os.path.join(
            ROOT, "bench", "configs", f"{name}.json"))["model"]

    mlp = model("mnist-mlp-paper")
    sizes = [784] + mlp["hidden"] + [mlp["n_classes"]]
    assert flops.mlp_params(sizes) == mlp["n_params"]
    m = model("mamba2-130m")
    d_in = m["expand"] * m["d_model"]
    H = d_in // m["headdim"]
    conv = d_in + 2 * m["ngroups"] * m["d_state"]
    per_layer = (m["d_model"] * (2 * d_in + 2 * m["d_state"] + H)
                 + d_in * m["d_model"] + m["d_conv"] * conv + conv
                 + 3 * H + d_in + m["d_model"])
    total = (m["n_layer"] * per_layer + m["vocab_padded"] * m["d_model"]
             + m["d_model"])
    assert total == m["n_params"] == 129_100_224


DRIVER_API = ("program", "call", "engine", "params", "s_rows", "stats",
              "close")


@pytest.mark.parametrize("kind", sorted(
    f[:-3] for f in os.listdir(os.path.join(ROOT, "bench", "drivers"))
    if f.endswith(".py")))
def test_every_driver_gives_what_the_harness_calls(kind):
    mod = harness.load_module(os.path.join(ROOT, "bench", "drivers",
                                           f"{kind}.py"))
    for fn in DRIVER_API:
        assert callable(getattr(mod, fn, None)), (kind, fn)


def test_unknown_traffic_kind_is_refused(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "odd.json").write_text('{"kind": "odd"}')
    monkeypatch.setattr(traffic, "BENCH", str(tmp_path))
    with pytest.raises(ValueError, match="no driver"):
        traffic.load("odd")
