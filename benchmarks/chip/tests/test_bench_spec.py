"""``BENCHMARK.json`` and every file it names keep the benchmark's rules."""
import json
import re

import pytest
from chipbench import spec as sp

SPEC = sp.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./\-]{1,200}$")
LINE = re.compile(r"^[^\t\n\r]{1,200}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head|expan|"
                   r"_dim$|_rank$|per_tok|top_k|d_model|d_ff|d_expert)")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (sp.ROOT / p).is_dir()
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(LINE.match(w) for w in cmd)
    named = [w for w in cmd if "/" in w]
    assert all(any(w.startswith(p + "/") for p in SPEC["paths"])
               for w in named)
    assert (sp.ROOT / named[0]).is_file()
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_and_reference(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(conf["name"]) and LINE.match(conf["source"])
    assert LINE.match(conf["why"])
    assert conf["file"] == f"benchmarks/chip/configs/{conf['name']}.json"
    data = sp.config(conf["name"])
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    assert data["reduced"] == conf["reduced"] and len(conf["reduced"]) <= 16
    for key in conf["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
    assert (sp.CHIP / "reference" / f"{data['reference']}.py").is_file()
    assert any(w["config"] == conf["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert LINE.match(cell["why"]) and cell["chips"] in (1, 4)
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    mix = sp.traffic(cell["traffic"])
    assert (sp.CHIP / "kinds" / f"{mix['kind']}.py").is_file()
    limits = sp.limits(cell["name"])
    assert limits and all(NAME.match(k) for k in limits)


def test_cells_are_distinct_and_four_chip_cells_few():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs) == len(set(CELLS))
    assert 1 <= len(CELLS) <= 24 and 1 <= len(SPEC["configs"]) <= 24
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2) and four <= 1


def test_metric_names_units_and_sources():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for cell in m.get("workloads", []):
            assert cell in CELLS
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= len(SPEC["end_to_end"]) <= 16
    assert 1 <= len(SPEC["per_layer"]) <= 128


def test_setup_time_is_reported_everywhere():
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.25


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_what_its_cells_report(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert LINE.match(metric["layer"])
    for cell in metric.get("workloads", CELLS):
        e2e = {m["name"] for m in sp.end_to_end(SPEC, cell)}
        assert metric["moves"] in e2e, (metric["name"], cell)
    reader = sp.metric_reader(metric["name"])
    assert callable(reader.read)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough(cell):
    e2e = {m["name"] for m in sp.end_to_end(SPEC, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert sp.per_layer(SPEC, cell)


def test_layers_named_alike():
    by_layer = {}
    for m in SPEC["per_layer"]:
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_harness_finds_added_cell_and_metric_by_name(tmp_path):
    """A later change adds a cell and a per-layer metric as new files and
    entries; the harness finds both by name and edits nothing."""
    cell = dict(SPEC["workloads"][0], name="added.cell")
    metric = {"name": "added_share", "unit": "%", "better": "higher",
              "source": "device_trace", "layer": "device",
              "moves": sp.end_to_end(SPEC, CELLS[0])[0]["name"],
              "workloads": ["added.cell"]}
    spec = dict(SPEC, workloads=SPEC["workloads"] + [cell],
                per_layer=SPEC["per_layer"] + [metric])
    assert sp.workload(spec, "added.cell")["traffic"] == cell["traffic"]
    assert [m["name"] for m in sp.per_layer(spec, "added.cell")] == [
        "added_share"]
    reader = tmp_path / "added_share.py"
    reader.write_text("def read(ctx, win, trace):\n    return 42.0\n")
    assert sp.load_module(reader).read(None, {}, None) == 42.0
