"""BENCHMARK.json against the benchmark's contract: keys, names, units,
bounds, and a file for every configuration, traffic mix and metric."""
import json
import os
import re

import pytest

import benchpath

with open(os.path.join(benchpath.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text \
        and "\t" not in text


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["command"]) <= 32 and all(_line(w) for w in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
    for word in BENCH["command"]:
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_run_seconds_fits_a_full_check():
    s = BENCH["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    cells = 24
    assert (2 + 14 * cells) * (s + 60) + cells * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("kind,entries", [
    ("config", BENCH["configs"]), ("workload", BENCH["workloads"]),
    ("end_to_end", BENCH["end_to_end"]), ("per_layer", BENCH["per_layer"])])
def test_entries_have_the_contract_keys_and_names(kind, entries):
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        extra = {"workloads"} if kind in ("end_to_end", "per_layer") else set()
        assert KEYS[kind] <= set(e) <= KEYS[kind] | extra, e["name"]
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e and kind in ("config", "workload") or key == "layer" and key in e:
                assert _line(e[key]), (e["name"], key)
    assert 1 <= len(entries) <= (128 if kind == "per_layer" else 24)


def test_cells_configs_and_metrics_are_consistent():
    configs = {c["name"]: c for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    pairs = set()
    for w in BENCH["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4) and _line(w["why"])
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(benchpath.BENCH, "traffic", w["traffic"] + ".json"))
        reports = [n for n, m in e2e.items() if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in reports and len(reports) >= 2, w["name"]
        layers = [m for m in BENCH["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]] if m["moves"] in reports else [])]
        assert layers, w["name"]
        for m in layers:
            assert m["moves"] in reports, (w["name"], m["name"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 2)
    for c in configs.values():
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        with open(os.path.join(benchpath.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert c["source"].startswith("https://") and _line(c["source"])
        for sub in ("reference", "flops"):
            assert os.path.exists(os.path.join(benchpath.BENCH, sub, cfg[sub] + ".py"))
        assert os.path.exists(os.path.join(benchpath.BENCH, "jobs", cfg.get("job", "glm") + ".py"))
        assert set(cfg["limits"]) >= {"gap_rel", "step_rel", "change_rel", "bits"}
    assert len({c["file"] for c in configs.values()}) == len(configs)
    layer_of = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert os.path.exists(os.path.join(benchpath.BENCH, "metrics", m["name"] + ".py"))
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in BENCH["workloads"]}
        layer_of.setdefault(m["layer"], m["layer"])
    for m in e2e.values():
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in BENCH["workloads"]}
