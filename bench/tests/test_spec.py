"""BENCHMARK.json is well formed and every cell resolves its files."""

import os
import re

import pytest

from bench import harness

ROOT = harness.ROOT
SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_entries_have_exactly_their_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
        for k in ("source", "why"):
            assert 1 <= len(c[k]) <= 200 and not set(c[k]) & {"\n", "\t"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}, m
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}, m


def test_names_units_and_bounds():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if "roofline" in m["name"]:  # a share of a roofline: <kernel>_roofline
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves(cell):
    w, cfg, mix, readers = harness.resolve(SPEC, cell)
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert cfg["rows"] * cfg["cols"] == cfg["qubits"]
    conf = {c["name"]: c for c in SPEC["configs"]}[w["config"]]
    assert set(conf["reduced"]) <= set(cfg)
    assert mix["devices"] == w["chips"]
    assert mix["ids_per_call"] == mix["slice_batch"] * mix["devices"]
    assert readers, "a cell reports at least one per-layer metric"
    for path in readers.values():
        assert callable(harness.load_reader(path))


def test_every_config_is_used_and_four_chip_cells_are_few():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_run_length_fits_the_full_check():
    # 2 + 14 runs per cell, each run_seconds + 60 s, 180 s per cell to
    # compile and 1200 s spare, with the full 24 cells
    cells = 24
    total = (2 + 14 * cells) * (SPEC["run_seconds"] + 60) + 180 * cells
    assert total + 1200 <= 43200


def test_peaks_table_has_the_v5e():
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")
    assert p["source"]
