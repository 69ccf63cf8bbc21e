"""Every entry of BENCHMARK.json resolves by name to its files, and the
file keeps to the benchmark's schema. A new configuration, traffic mix or
per-layer metric is new files plus new entries: these tests cover it
with no change."""
import json
import re

import pytest

import harness

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1].startswith("bench/")
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def _line(text) -> bool:
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def test_names_units_bounds():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and _line(m["layer"])
        assert set(m.get("workloads", cells)) <= cells
    for x in BENCH["workloads"] + BENCH["configs"]:
        assert _line(x["why"])
    assert all(_line(c["source"]) for c in BENCH["configs"])
    assert all(_line(w) for w in BENCH["command"])


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(w):
    cell = harness.load_cell(w["name"], BENCH)
    assert cell.chips in (1, 4)
    assert cell.config["name"] == w["config"]
    harness.load_module("drivers", cell.config["kind"])
    ref = harness.load_module("reference", cell.config["reference"])
    ref.dims_from_config(cell.config)
    assert cell.traffic["name"] == w["traffic"]
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_module("metrics", m["name"]).read)


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(c):
    assert c["file"].startswith("bench/configs/")
    conf = harness.load_json(harness.ROOT / c["file"])
    assert conf["name"] == c["name"]
    assert conf["source"] == c["source"]
    assert conf["reduced"] == c["reduced"]
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])


def test_peaks_table():
    peaks = harness.load_json(harness.BENCH / "peaks.json")
    assert "TPU v5e" in peaks["source"]
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit):
        harness.peaks("TPU v9 imaginary")


def test_metric_workloads_key():
    """A metric with ``workloads`` belongs to those cells alone; one
    without it, to every cell that reports the metric it moves."""
    only = {"name": "x", "moves": "a", "workloads": ["c1"]}
    every = {"name": "y", "moves": "a"}
    assert harness._applies(only, "c1", {"a"})
    assert not harness._applies(only, "c2", {"a"})
    assert harness._applies(every, "c2", {"a"})
    assert not harness._applies(every, "c2", {"b"})
    assert harness._applies(every, "c2", None)
