"""BENCHMARK.json keeps to the benchmark's contract: allowed characters in
every name and unit, the keys of each entry, and that every cell, metric
and configuration it names has its files under bench/, and that no
configuration lists a width among its reductions."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def spec():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and not re.search(
        r"[\n\r\t]", s)


def test_top_level(spec):
    assert set(spec) == {"command", "paths", "run_seconds", *KEYS}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    rs = spec["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits 43,200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_entries_and_names(spec):
    for group, keys in KEYS.items():
        names = [e["name"] for e in spec[group]]
        assert len(names) == len(set(names)), group
        for e in spec[group]:
            extra = {"workloads"} if group in ("end_to_end",
                                               "per_layer") else set()
            assert keys <= set(e) <= keys | extra, (group, e["name"])
            assert NAME.fullmatch(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.fullmatch(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e:
                    assert line(e[k]), (e["name"], k)
    for w in spec["workloads"]:
        assert NAME.fullmatch(w["config"]) and NAME.fullmatch(w["traffic"])
        assert w["chips"] in (1, 4)
    for c in spec["configs"]:
        assert len(c["reduced"]) <= 16
        assert all(NAME.fullmatch(k) for k in c["reduced"])


def test_files_exist_under_paths(spec):
    cfg_files = [c["file"] for c in spec["configs"]]
    assert len(cfg_files) == len(set(cfg_files))
    for f in cfg_files:
        assert f.startswith("bench/") and os.path.isfile(
            os.path.join(ROOT, f))
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    for w in spec["workloads"]:
        for sub in (("traffic", w["traffic"]), ("limits", w["name"])):
            assert os.path.isfile(os.path.join(ROOT, "bench", sub[0],
                                               sub[1] + ".json")), sub
    for m in spec["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py")), m["name"]
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))


#: keys of a configuration file that are widths, which ``reduced`` may
#: never name
WIDTHS = {"d_model", "d_ff", "rpe_hidden", "gtu_expand", "glu_dim"}


def test_reduced_names_keys_of_the_file_and_no_width(spec):
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        for k in c["reduced"]:
            assert k in cfg, (c["name"], k)
            assert k not in WIDTHS and not k.endswith(("_dim", "_rank")), k
        assert cfg["name"] == c["name"]


def test_metrics_and_cells(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in spec["workloads"]}

    def reported(m, cell):
        return cell in m.get("workloads", cells)

    for m in spec["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in cells
            assert reported(e2e[m["moves"]], cell), (m["name"], cell)
    layers = {}
    for m in spec["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for cell in cells:
        mine = [n for n, m in e2e.items() if reported(m, cell)]
        assert "setup_s" in mine and len(mine) >= 2, cell
        assert any(reported(m, cell) for m in spec["per_layer"]), cell
    four = sum(1 for w in spec["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)


def test_mfu_beside_every_roofline(spec):
    mfu = {m["moves"] for m in spec["per_layer"] if "mfu" in m["name"]}
    for m in spec["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["moves"] in mfu
