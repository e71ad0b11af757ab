"""Everything ``BENCHMARK.json`` names resolves to its file, each
per-layer metric moves an end-to-end metric that all its cells report,
and the file keeps its fixed shape: keys, names, units and bounds."""
import _paths  # noqa: F401
import os
import re

import pytest

from chipbench import spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"][1] == "chipbench/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(spec.CHECKOUT,
                                        "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    r = spec.resolve(cell, BENCH)
    entry = r["entry"]
    assert os.path.isfile(spec.cell_path(cell))
    assert os.path.isfile(spec.config_path(entry["config"]))
    assert os.path.isfile(spec.traffic_path(entry["traffic"]))
    assert os.path.isfile(spec.reference_path(r["config"]["reference"]))
    assert os.path.isfile(spec.arch_path(r["config"]["reference"]))
    conf = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert conf["file"] == f"chipbench/configs/{entry['config']}.json"
    for m in r["end_to_end"] + r["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)
    reported = {m["name"] for m in r["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert r["per_layer"]


CONFIG_FILES = sorted(f[:-len(".json")] for f in os.listdir(
    os.path.join(spec.BENCH_DIR, "configs")) if f.endswith(".json"))


@pytest.mark.parametrize("config", CONFIG_FILES)
def test_config_names_its_family_files(config):
    family = spec.load_json(spec.config_path(config))["reference"]
    assert os.path.isfile(spec.reference_path(family))
    assert os.path.isfile(spec.arch_path(family))


def test_per_layer_metrics_move_what_their_cells_report():
    for m in BENCH["per_layer"]:
        for cell in m["workloads"]:
            assert cell in CELLS
            r = spec.resolve(cell, BENCH)
            assert m["moves"] in {e["name"] for e in r["end_to_end"]}


def test_shares_have_an_mfu_beside_them():
    shares = [m for m in BENCH["per_layer"] if m["name"].endswith(
        "_roofline")]
    mfu_moves = {m["moves"] for m in BENCH["per_layer"] if "mfu" in
                 m["name"]}
    for m in shares:
        assert m["unit"] == "%" and m["moves"] in mfu_moves


def test_every_config_is_used_and_reduced_keys_differ():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        data = spec.load_json(spec.config_path(c["name"]))
        assert data["source"] == c["source"]
        assert isinstance(data["engine"], dict)
        for k in c["reduced"]:
            assert data["published"][k] != data[k]


def test_each_pair_of_config_and_traffic_is_one_cell():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_deployments_of_one_source_hold_the_same_model():
    # two deployments of one published model differ only in how they are
    # served, never in a size or constant of the model
    served = {"deployment", "engine", "assumed"}
    by_source = {}
    for c in BENCH["configs"]:
        data = spec.load_json(spec.config_path(c["name"]))
        model = {k: v for k, v in data.items() if k not in served}
        by_source.setdefault(c["source"], []).append(model)
    for models in by_source.values():
        assert all(m == models[0] for m in models)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("no such chip")
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
