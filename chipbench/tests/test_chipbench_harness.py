"""The harness finds every cell, configuration, mix, metric and reference
in ``BENCHMARK.json`` by name, and a new one, a decoder family too, is
added as new files plus a new entry, with no edit to an existing file."""

import json
import os
import shutil

import pytest

from chipbench_paths import BENCH, REPO, SERVED_RPS, family_root

import run as R
from lib.cells import load_cell

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
#: The harness's shared files, which no new cell, mix, metric,
#: configuration or decoder family edits.
SHARED = ("run.py", "lib/cells.py", "lib/traffic.py", "lib/weights.py",
          "lib/flops.py", "lib/readers.py", "lib/check.py")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = load_cell(name, REPO)
    assert cell.chips in (1, 4)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    ref = cell.reference()
    assert ref.__file__.startswith(BENCH)
    assert callable(ref.decode)
    assert isinstance(ref.PROGRAM_KEYS, dict) and ref.PROGRAM_KEYS
    for name in ("latent_hwc", "weight_tree", "calls"):
        assert callable(getattr(ref, name))


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
              for p in ("lib/cells.py", "run.py", "lib/traffic.py",
                        "lib/weights.py", "lib/flops.py", "lib/readers.py")}
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


def _run_family(root, trace):
    args = R.parse(["--workload", "copy.scan", "--seed", str(2 ** 35 + 19),
                    "--seconds", "0.5", "--trace", str(trace)])
    return R.run(args, load_cell("copy.scan", root), require_tpu=False,
                 compile_cache=False)


def test_new_family_is_new_files(tmp_path):
    """A decoder family that exists only in the checkout, as a module, a
    configuration, a mix and a cell: a run takes the program check, the
    weight tree, the latent shape and the calls from that module, with
    every shared file of the harness as it is."""
    root = family_root(tmp_path, sentinel_latent_channels=4)
    module = tmp_path / "chipbench" / "references" / "kl_copy.py"
    seen = module.with_suffix(".seen")
    assert load_cell("copy.scan", root).reference().__file__ == str(module)
    res = _run_family(root, trace=0)
    assert res["correct"] is True, res["checks"]
    assert sorted(seen.read_text().split()) == ["latent_hwc", "weight_tree"]
    seen.unlink()
    res = _run_family(root, trace=1)
    assert res["correct"] is True, res["checks"]
    assert "calls" in seen.read_text().split()
    for p in SHARED:
        assert (tmp_path / "chipbench" / p).read_bytes() == \
            open(os.path.join(BENCH, p), "rb").read()


def test_new_family_checks_the_program(tmp_path):
    """The family's ``PROGRAM_KEYS`` decides what the program's decoder
    must match: a key that only the new family maps, stated otherwise in
    its configuration, refuses the run and names the key."""
    root = family_root(tmp_path, sentinel_latent_channels=5)
    with pytest.raises(ValueError, match="sentinel_latent_channels=5"):
        _run_family(root, trace=0)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        load_cell("no-such-cell", REPO)
