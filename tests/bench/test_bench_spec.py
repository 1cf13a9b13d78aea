"""BENCHMARK.json resolves, by name, to files of the benchmark's own."""

import json
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = spec.benchmark()


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves(cell):
    assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
    cfg = spec.config(cell["config"])
    assert cfg["name"] == cell["config"]
    mix = spec.traffic(cell["traffic"])
    assert mix["free"] in ("withdraw", "complete")
    assert set(spec.limits(cell["name"])) >= {"factor_gap", "alpha_gap",
                                              "acq_excess", "bad_configs"}
    reported = {m["name"] for m in spec.cell_metrics(cell["name"], "end_to_end")}
    assert "setup_s" in reported and len(reported) >= 2
    for m in spec.cell_metrics(cell["name"], "per_layer"):
        assert m["moves"] in reported, (m["name"], cell["name"])


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(entry):
    cfg = spec.config(entry["name"])
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    assert set(entry["reduced"]) <= set(cfg)
    assert any(c["config"] == entry["name"] for c in BENCH["workloads"])
    spec.search_space(cfg)
    spec.service_config(cfg)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_has_reader(metric):
    assert NAME.match(metric["name"])
    assert callable(spec.reader(metric["name"]))


def test_unknown_device_has_no_peaks():
    assert spec.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(spec.SpecError):
        spec.peaks("cpu")


def test_file_is_small_json():
    with open(spec.ROOT / "BENCHMARK.json", "rb") as fh:
        raw = fh.read()
    assert len(raw) < 64 * 1024
    json.loads(raw)
