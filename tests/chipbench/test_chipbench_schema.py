"""BENCHMARK.json against the benchmark's contract: every cell resolves its
parts by name, names and units use only the allowed characters, and every
per-layer metric's cells report the end-to-end metric it moves."""
from __future__ import annotations

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as bench  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p)
        assert os.path.isdir(os.path.join(ROOT, p))
    assert any(SPEC["command"][1].startswith(p + "/") for p in SPEC["paths"])


def test_names_and_units_use_allowed_characters():
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in METRICS]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for kind in (SPEC["configs"], SPEC["workloads"], METRICS):
        assert len({x["name"] for x in kind}) == len(kind)
    for m in METRICS:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for text in ([c["source"] for c in SPEC["configs"]]
                 + [x["why"] for x in SPEC["configs"] + SPEC["workloads"]]
                 + [m["layer"] for m in SPEC["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_bounds():
    assert {m["name"] for m in SPEC["end_to_end"]} >= {"setup_s"}
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    (setup,) = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert setup["bound"] == 0.25
    assert all("bound" not in m for m in SPEC["per_layer"])


COMMON_KEYS = {"app", "source", "assumed", "reduced", "chips", "max_batch",
               "max_wait_ms", "devices", "extra_workers"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_every_part_by_name(cell):
    c = bench.resolve(cell, SPEC)
    (w,) = [w for w in SPEC["workloads"] if w["name"] == cell]
    assert c.chips == w["chips"] == c.config["chips"]
    assert os.path.isfile(os.path.join(ROOT, "chipbench", "apps",
                                       c.config["app"] + ".py"))
    assert COMMON_KEYS | set(c.app.REQUIRED) <= set(c.config)
    for part in ("inputs", "build", "check"):
        assert callable(getattr(c.app, part))
    load = os.path.join(ROOT, "chipbench", "loadgen",
                        c.traffic["kind"] + ".py")
    assert os.path.isfile(load)
    for m in c.end_to_end + c.per_layer:
        assert callable(bench._module("metrics", m["name"]).read)
    assert "setup_s" in {m["name"] for m in c.end_to_end}
    assert len(c.end_to_end) >= 2 and len(c.per_layer) >= 1


def test_resolve_refuses_a_configuration_without_an_app(tmp_path):
    c = SPEC["configs"][0]
    (cell, *_) = [w["name"] for w in SPEC["workloads"]
                  if w["config"] == c["name"]]
    with open(os.path.join(ROOT, c["file"])) as f:
        config = json.load(f)
    del config["app"]
    path = tmp_path / "no-app.json"
    path.write_text(json.dumps(config))
    spec = dict(SPEC, configs=[dict(c, file=str(path))])
    with pytest.raises(KeyError, match="names no app"):
        bench.resolve(cell, spec)


def test_configs_are_files_of_their_own_under_paths():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for c in SPEC["configs"]:
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


def test_per_layer_cells_report_the_metric_they_move():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)


def test_at_most_half_the_cells_take_four_chips():
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in SPEC["workloads"])
    assert len(four) <= max(1, len(CELLS) // 2)
