"""BENCHMARK.json against the rules a later PR has to keep, and the
data-driven layout: every cell's files are found by name, and a new mix
takes only new files and new manifest entries."""
import json
import re
import shutil

import pytest

from chipbench_tiny import ROOT
from benchmarks.chip import run_cell as RC

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert 1 <= bench["run_seconds"] <= 51
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/")
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["traffic"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for m in bench["end_to_end"]
                    + bench["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)


def test_every_cell_reports_what_its_metrics_move(bench):
    cells = [w["name"] for w in bench["workloads"]]
    reports = {c: {m["name"] for m in bench["end_to_end"]
                   if c in m.get("workloads", cells)} for c in cells}
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        for c in m["workloads"]:
            assert m["moves"] in reports[c], (m["name"], c)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in cells:
        assert any(c in m["workloads"] for m in bench["per_layer"]), c


def test_every_cell_and_metric_is_found_by_name(bench):
    for w in bench["workloads"]:
        cell = RC.load_cell(ROOT, w["name"])
        assert cell.config["chips"] == w["chips"]
        lo, hi = cell.mix["prompt_tokens"]
        assert lo <= hi and cell.mix["output_tokens"][0] >= 2
        assert cell.sizing["limits"]["served_logit_gap"] > 0
    for m in bench["per_layer"]:
        mod = RC.metric_reader(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == \
            (m["layer"], m["unit"], m["moves"], m["source"])


def test_a_new_mix_needs_only_new_files(tmp_path, bench):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    here = tmp_path / "benchmarks" / "chip"
    (here / "traffic" / "throwaway.json").write_text(json.dumps({
        "arrivals": "stratified_poisson", "rate_per_s": 1.0,
        "stratum_s": 5, "prompt_tokens": [100, 200],
        "output_tokens": [10, 20],
        "ramp_s": 5, "drain_cap_s": 60, "check_requests": 2}))
    name = bench["workloads"][0]["config"] + ".throwaway"
    (here / "cells" / f"{name}.json").write_text(json.dumps({
        "max_batch": 4, "reference_rows": 2,
        "limits": {"served_logit_gap": 1.0}}))
    new = json.loads((tmp_path / "BENCHMARK.json").read_text())
    new["workloads"].append({"name": name, "config": bench["workloads"][0]
                             ["config"], "traffic": "throwaway",
                             "chips": 1, "why": "throwaway"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    cell = RC.load_cell(tmp_path, name)
    assert cell.mix["rate_per_s"] == 1.0
    assert RC.runtime_fields(cell)["max_prompt_bucket"] == 256
    assert [e["name"] for e in cell.end_to_end] == ["setup_s"]
    changed = [p for p, b in before.items()
               if p.name != "BENCHMARK.json" and p.read_bytes() != b]
    assert changed == []


def test_a_full_check_fits_its_time(bench):
    # 2 + 14 runs per cell of run_seconds + 60 s, 2 x 90 s of compiling per
    # cell and 1200 s spare, for the most cells a later PR may add
    n, rs = 24, bench["run_seconds"]
    assert (2 + 14 * n) * (rs + 60) + n * 180 + 1200 <= 43200
