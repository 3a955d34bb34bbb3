"""BENCHMARK.json against the rules a later PR has to keep, and the
data-driven layout: every cell's files are found by name, and a new mix or
a new model family takes only new files and new manifest entries."""
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
        dims = cell.family.dims(cell.config)
        assert dims.vocab == cell.config["vocab_size"]
        assert cell.config["num_key_value_heads"] % cell.config["tp"] == 0
    for m in bench["per_layer"]:
        mod = RC.metric_reader(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == \
            (m["layer"], m["unit"], m["moves"], m["source"])


def _copy(tmp_path):
    """A copy of the benchmark's files; returns each file's bytes."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}


def _changed(before):
    return [p for p, b in before.items()
            if p.name != "BENCHMARK.json" and p.read_bytes() != b]


def test_a_new_mix_needs_only_new_files(tmp_path, bench):
    before = _copy(tmp_path)
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
    assert _changed(before) == []


TOY_FAMILY = '''
from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    d: int
    vocab: int


def dims(model):
    return Dims(model["hidden_size"], model["vocab_size"])


def check(cfg, model):
    pass


def seed_key(seed):
    return seed


def program_params(key, model, dtype):
    return {}


def served_gaps(seed, model, requests, *, rows, seq_len, out_len,
                controls=()):
    return 0.0, (0.0,) * len(controls)


def prefill_flops(m, plen, tp=1):
    return 10 * plen // tp


def decode_token_flops(m, ctx, tp=1):
    return 3 * m.d // tp


def attn_decode(m, ctx, tp=1):
    return ctx // tp, 2 * ctx // tp
'''


def test_a_new_model_type_needs_only_new_files(tmp_path, bench):
    """A configuration of another family enters as new files (its family
    module, its configuration, its cell) and new manifest entries."""
    before = _copy(tmp_path)
    here = tmp_path / "benchmarks" / "chip"
    (here / "families" / "toy.py").write_text(TOY_FAMILY)
    (here / "configs" / "toy-1chip.json").write_text(json.dumps({
        "model_type": "toy", "hidden_size": 64, "vocab_size": 512,
        "arch": "toy", "chips": 1, "tp": 1,
        "runtime": {"paged": True, "page_size": 16}}))
    name = "toy-1chip.chat"
    (here / "cells" / f"{name}.json").write_text(json.dumps({
        "max_batch": 4, "reference_rows": 2,
        "limits": {"served_logit_gap": 1.0}}))
    new = json.loads((tmp_path / "BENCHMARK.json").read_text())
    new["configs"].append({"name": "toy-1chip", "source": "toy",
                           "file": "benchmarks/chip/configs/toy-1chip.json",
                           "reduced": [], "why": "toy"})
    new["workloads"].append({"name": name, "config": "toy-1chip",
                             "traffic": "chat", "chips": 1, "why": "toy"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    cell = RC.load_cell(tmp_path, name)
    assert cell.family.__file__ == str(here / "families" / "toy.py")
    dims = cell.family.dims(cell.config)
    assert dims.vocab == 512
    # the harness books work by the family's counts, not work.py's
    rec = RC.Record(dims, 1, 4)
    rec.reqs = {1: RC.Req(1, 0.0, 600, 8, None)}
    rec.traced_tokens = [(1, 0, 3)]
    RC.book_work(rec, cell.family)
    assert rec.work == {"prefill_flops": 6000, "prefill_tokens": 600,
                        "attn_flops": 601 + 602, "attn_bytes": 2 * 1203,
                        "decode_flops": 2 * 192, "decode_tokens": 2}
    assert _changed(before) == []


# The entries that admit the queued four-chip cell; its files are in the
# tree, and it joins BENCHMARK.json once its runs on a v5e-4 are made.
TP4_CONFIG = {"name": "qwen2-7b-tp4",
              "source": "https://huggingface.co/Qwen/Qwen2-7B",
              "file": "benchmarks/chip/configs/qwen2-7b-tp4.json",
              "reduced": [], "why": "Qwen2 7B uncut at tensor parallel 4"}
TP4_CELL = {"name": "qwen2-7b-tp4.chat-batch", "config": "qwen2-7b-tp4",
            "traffic": "chat-batch", "chips": 4,
            "why": "tensor-parallel all-reduces, paged kernel under shard_map"}
TP4_METRICS = {"decode_mfu.tp4": ("model step", "higher"),
               "paged_attn_roofline.tp4": ("kernels", "higher"),
               "collective_share.tp4": ("device", "lower")}


def test_the_queued_tp4_cell_needs_only_manifest_entries(tmp_path, bench):
    before = _copy(tmp_path)
    new = json.loads((tmp_path / "BENCHMARK.json").read_text())
    new["configs"].append(TP4_CONFIG)
    new["workloads"].append(TP4_CELL)
    out = next(m for m in new["end_to_end"]
               if m["name"] == "output_tokens_per_s")
    out["workloads"].append(TP4_CELL["name"])
    new["per_layer"] += [
        {"name": n, "unit": "%", "better": better, "source": "device_trace",
         "layer": layer, "moves": "output_tokens_per_s",
         "workloads": [TP4_CELL["name"]]}
        for n, (layer, better) in TP4_METRICS.items()]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    cell = RC.load_cell(tmp_path, TP4_CELL["name"])
    model = cell.config
    assert model["chips"] == model["tp"] == 4 and model["reduced"] == {}
    assert model["num_hidden_layers"] == 28
    assert model["num_key_value_heads"] % model["tp"] == 0
    assert cell.family.dims(model).kv_heads // model["tp"] == 1
    assert RC.runtime_fields(cell)["max_batch"] == 64
    assert cell.mix["arrivals"] == "backlog"
    assert [e["name"] for e in cell.end_to_end] == ["output_tokens_per_s",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == list(TP4_METRICS)
    for m in cell.per_layer:
        mod = RC.metric_reader(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == \
            (m["layer"], m["unit"], m["moves"], m["source"])
    assert _changed(before) == []


@pytest.mark.parametrize("model_type", [None, "nonesuch"])
def test_a_config_without_a_family_fails_naming_the_file(tmp_path, bench,
                                                         model_type):
    _copy(tmp_path)
    conf = bench["configs"][0]
    path = tmp_path / conf["file"]
    model = json.loads(path.read_text())
    del model["model_type"]
    if model_type:
        model["model_type"] = model_type
    path.write_text(json.dumps(model))
    name = next(w["name"] for w in bench["workloads"]
                if w["config"] == conf["name"])
    with pytest.raises((ValueError, FileNotFoundError)) as err:
        RC.load_cell(tmp_path, name)
    assert str(path) in str(err.value)
    if model_type:
        assert str(tmp_path / "benchmarks" / "chip" / "families"
                   / "nonesuch.py") in str(err.value)


def test_a_full_check_fits_its_time(bench):
    # 2 + 14 runs per cell of run_seconds + 60 s, 2 x 90 s of compiling per
    # cell and 1200 s spare, for the most cells a later PR may add
    n, rs = 24, bench["run_seconds"]
    assert (2 + 14 * n) * (rs + 60) + n * 180 + 1200 <= 43200
