"""The harness finds every cell, configuration, mix, metric and reference
in ``BENCHMARK.json`` by name, and a new one is added as new files plus a
new entry, with no edit to an existing file."""

import json
import os
import shutil

import pytest

from chipbench_paths import BENCH, REPO, SERVED_RPS

from lib.cells import load_cell

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = load_cell(name, REPO)
    assert cell.chips in (1, 4)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    assert callable(cell.reference().decode)
    ref = cell.reference()
    assert ref.__file__.startswith(BENCH)


def test_every_metric_has_a_reader_and_cell():
    names = {w["name"] for w in BENCHMARK["workloads"]}
    for m in BENCHMARK["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py"))
        assert set(m["workloads"]) <= names
    for c in BENCHMARK["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]


def test_new_files_extend_the_benchmark(tmp_path):
    """A later change adds a mix, a metric and a cell as files and
    entries; the harness picks them up with every existing file as is."""
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(os.path.join(BENCH, p), "rb").read()
              for p in ("lib/cells.py", "run.py", "lib/traffic.py")}
    mix = json.load(open(os.path.join(BENCH, "mixes",
                                      "scan_closed.json")))
    mix["corpus"] = 512
    (tmp_path / "chipbench" / "mixes" / "scan_512.json").write_text(
        json.dumps(mix))
    (tmp_path / "chipbench" / "metrics" / "calls_per_s.py").write_text(
        "def read(ctx):\n    return len(ctx.window.calls) / ctx.window.end_s\n")
    b = json.loads(json.dumps(BENCHMARK))
    b["workloads"].append({"name": "sd15-512.scan", "config": "sd15_vae-512",
                           "traffic": "scan_512", "chips": 1, "why": "t"})
    rate = [m for m in b["end_to_end"] if m["name"] == "served_rps"]
    if rate:
        rate[0]["workloads"].append("sd15-512.scan")
    else:
        b["end_to_end"].insert(0, SERVED_RPS | {"workloads":
                                                ["sd15-512.scan"]})
    b["per_layer"].append({"name": "calls_per_s", "unit": "1/s",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine and decode batcher",
                           "moves": "served_rps",
                           "workloads": ["sd15-512.scan"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = load_cell("sd15-512.scan", str(tmp_path))
    assert cell.mix["corpus"] == 512
    assert cell.config["decoder"] == "sd15_vae"
    assert [m["name"] for m in cell.end_to_end] == ["served_rps", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["calls_per_s"]
    assert cell.reader("calls_per_s").read is not None
    for p, data in before.items():
        assert open(tmp_path / "chipbench" / p, "rb").read() == data


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        load_cell("no-such-cell", REPO)
