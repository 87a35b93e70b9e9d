"""BENCHMARK.json against the files it names (harness/cells.py finds
everything by name): every cell loads with its configuration, its traffic
mix and its metric lists, every metric has a reader, every `workloads`
entry names a cell. A cell or metric added as an entry without its file
fails here, not at the first run on the chip."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

from harness import cells  # noqa: E402

with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_the_pair_cell_loads_with_its_traffic_and_metric_lists():
    cell = cells.load_cell("cu-minimal32.pair")
    assert cell.chips == 1
    assert cell.config["name"] == "committee-minimal32-k14"
    assert (cell.traffic["clients"], cell.traffic["concurrency"]) == (2, 2)
    # the second client starts late enough to be out of phase and early
    # enough to send inside any window a run measures
    assert 0 < cell.traffic["stagger_s"] < BENCH["run_seconds"]
    assert [m["name"] for m in cell.end_to_end] == ["prove_s", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    own = {"queue_wait_s.pair", "no_inflight_s.pair"}
    assert own | {"hbm_peak_gb.serial", "backend_calls"} <= names
    serial = {m["name"] for m in cells.load_cell(
        "cu-minimal32.serial").per_layer}
    assert not own & serial
    # a job's own seconds hold its waits for the job beside it: no metric
    # of them carries this cell under the name it has with one client
    assert not {"msm_inflight_s", "ntt_inflight_s", "commit_advice_s",
                "quotient_s", "witness_s", "host_only_s"} & names


def test_an_unknown_cell_is_no_result():
    with pytest.raises(cells.BenchError, match="unknown workload"):
        cells.load_cell("cu-minimal32.nonesuch")


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_has_its_files_and_reports_what_the_contract_asks(name):
    cell = cells.load_cell(name)             # configuration + traffic exist
    assert cell.chips in (1, 4)
    assert {"clients", "concurrency", "why"} <= set(cell.traffic)
    for kind in ("requests", "reference"):
        cells.load_plugin(kind, cell.config["circuit"])
    cells.load_plugin("servers", cell.config.get("server", "single"))
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:                 # it moves a metric the cell has
        assert m["moves"] in e2e, m["name"]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_reader_and_names_cells_that_exist(metric):
    assert callable(cells.load_plugin("metrics", metric["name"]).read)
    assert set(metric.get("workloads", ())) <= set(CELLS)
    if "workloads" in metric:
        assert metric["workloads"], "a metric no cell reports"


def test_the_file_keeps_to_the_contracts_counts():
    assert 1 <= len(CELLS) <= 24 and len(set(CELLS)) == len(CELLS)
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= max(1, len(CELLS) // 2)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200, c["name"]
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200, w["name"]
