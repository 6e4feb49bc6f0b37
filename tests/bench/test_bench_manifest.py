"""The benchmark's manifest and the files it names, found by name."""
import json
import re

import pytest

from bench import manifest

M = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_manifest_has_exactly_the_contract_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"] == ["python3", "bench/run.py"]
    assert 1 <= M["run_seconds"] <= 51


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda c: c["name"])
def test_every_cell_finds_its_files(cell):
    config = manifest.load_config(cell["config"])
    assert config["name"] == cell["config"]
    traffic = manifest.load_traffic(cell["traffic"])
    assert hasattr(manifest.load_driver(traffic["kind"]), "window")
    assert hasattr(manifest.load_reference(cell["config"]), "init_params")
    assert cell["chips"] in (1, 4) and NAME.match(cell["name"])


@pytest.mark.parametrize("metric", M["end_to_end"] + M["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_finds_its_reader(metric):
    assert NAME.match(metric["name"])
    assert callable(manifest.load_metric(metric["name"]).read)
    for w in metric.get("workloads", []):
        manifest.find_cell(M, w)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for cell in M["workloads"]:
        e2e = manifest.metrics_of(M["end_to_end"], cell["name"])
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.metrics_of(M["per_layer"], cell["name"])


def test_per_layer_metrics_move_a_metric_their_cells_report():
    for m in M["per_layer"]:
        for w in m["workloads"]:
            assert m["moves"] in manifest.metrics_of(M["end_to_end"], w)


def test_config_files_hold_what_the_manifest_says(tmp_path):
    for c in M["configs"]:
        body = json.loads((manifest.ROOT / c["file"]).read_text())
        assert body["source"] == c["source"]
        assert body["limits"], "every configuration states its limits"


@pytest.mark.parametrize("bad", ["../BENCHMARK", "a/b", "", "x" * 65])
def test_a_name_outside_the_rules_is_refused(bad):
    with pytest.raises(ValueError):
        manifest.load_metric(bad)


def test_metrics_of_filters_by_workload():
    entries = [{"name": "a"}, {"name": "b", "workloads": ["w1"]}]
    assert manifest.metrics_of(entries, "w1") == ["a", "b"]
    assert manifest.metrics_of(entries, "w2") == ["a"]
