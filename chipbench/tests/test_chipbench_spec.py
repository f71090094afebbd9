"""BENCHMARK.json names files that exist and keeps to its own rules:
names, units, metric keys, the cells each metric is read in."""
import json
import re

import pytest

from chipbench import spec

BENCH = json.loads((spec.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        for n in names:
            assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_what_it_must():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        cell = spec.cell(w["name"], BENCH)
        mine = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in mine and len(mine) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in mine, (w["name"], m["name"])
            assert e2e[m["moves"]]
        assert w["chips"] == 1


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(c):
    conf = spec.config(c["name"])
    assert c["file"] == f"chipbench/configs/{c['name']}.json"
    assert conf["source"] == c["source"]
    assert sorted(conf["reduced"]) == sorted(c["reduced"])
    pub, m, keys = conf["published"], conf["model"], conf["model_keys"]
    # every model value comes from the published config, or is reduced
    # or assumed, as the file says
    for k, src in keys.items():
        if src == "assumed":
            assert k in conf["assumed"]
        elif src.endswith("(reduced)"):
            assert src.split()[0] in conf["reduced"]
        else:
            assert pub[src] == m[k], (k, src)
    assert m["head_dim"] * m["n_heads"] == pub["hidden_size"]
