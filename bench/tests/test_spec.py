"""BENCHMARK.json against the benchmark contract's names, units and
limits, and every file its names point to."""
import json
import re

import helpers

SPEC = json.loads((helpers.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"},
}


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert len(SPEC["command"]) <= 32 and all(one_line(w) for w in SPEC["command"])
    assert any(w.startswith(SPEC["paths"][0] + "/") for w in SPEC["command"])
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_entries_names_and_units():
    names = set()
    for kind, keys in ENTRY_KEYS.items():
        for e in SPEC[kind]:
            assert set(e) <= keys, (kind, set(e) - keys)
            assert NAME.match(e["name"]), e["name"]
            names.add(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e:
                    assert one_line(e[k]), (e["name"], k)
    assert len(names) == sum(len(SPEC[k]) for k in ENTRY_KEYS)


def test_cells_configs_and_metrics():
    configs = {c["name"]: c for c in SPEC["configs"]}
    cells = {w["name"]: w for w in SPEC["workloads"]}
    assert 1 <= len(configs) <= 24 and 1 <= len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == len(cells)
    for w in cells.values():
        assert w["chips"] in (1, 4) and w["config"] in configs
        assert NAME.match(w["traffic"])
        assert (helpers.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        limits = json.loads((helpers.BENCH / "limits" / f"{w['name']}.json").read_text())
        assert limits["limits"] and all(v >= 0 for v in limits["limits"].values())
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    for c in configs.values():
        assert c["file"].startswith("bench/")
        cfg = json.loads((helpers.REPO / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg) and len(c["reduced"]) <= 16
        assert cfg["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in cells.values())
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
        assert (helpers.BENCH / "metrics" / f"{m['name']}.py").is_file()
    for w in cells:        # every cell: setup_s, another e2e, a layer metric
        assert sum(w in m.get("workloads", cells) for m in e2e.values()) >= 2
        assert any(w in m.get("workloads", cells) for m in SPEC["per_layer"])


def test_layers_are_named_alike():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all(one_line(x) for x in layers)


def test_rooflines_and_mfu_named_by_the_contract():
    for m in SPEC["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        if "roofline" in m["name"]:
            assert m["name"].split(".")[0].endswith("_roofline")
