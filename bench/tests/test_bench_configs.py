"""BENCHMARK.json and the configuration files: every name resolves to a
file of its own, and every configuration file states what the program
runs."""
import json
import pathlib
import re

import pytest

from bench import harness

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_what_is_run(cfg):
    from repro.configs import get_config

    c = json.loads((REPO / cfg["file"]).read_text())
    assert c["name"] == cfg["name"]
    assert harness.config_mismatches(get_config(c["arch"]), c) == []
    assert sorted(cfg["reduced"]) == sorted(c["reduced"]) == \
        sorted(c["departures"])
    assert (REPO / "bench" / "reference" / f"{c['reference']}.py").is_file()


def _starcoder2():
    return json.loads((REPO / "bench" / "configs"
                       / "starcoder2-7b-tp4.json").read_text())


@pytest.mark.parametrize("change", [
    {"norm_type": "layernorm"},         # the program has RMSNorm only
    {"use_bias": True},                 # nor biases on every linear layer
    {"norm_type": None},                # left unstated
    {"departures": {"max_len": {"published": 4096}}},       # not compared
    {"departures": {"rope_theta": {"published": 10000.0}}},  # no departure
], ids=["norm", "bias", "unstated", "uncompared", "not-departed"])
def test_config_check_catches_a_false_statement(change):
    from repro.configs import get_config

    c = _starcoder2()
    cfg = get_config(c["arch"])
    c.update(change)
    c = {k: v for k, v in c.items() if v is not None}
    assert harness.config_mismatches(cfg, c)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_name_resolves(w):
    cell = harness.resolve(REPO, w["name"])
    assert cell.traffic["kind"] and harness.generator(cell.traffic["kind"])
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.reader(m["name"]).read)
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_names_units_and_arrows():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads",
                                                    m["workloads"]))
