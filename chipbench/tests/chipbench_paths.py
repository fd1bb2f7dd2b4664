"""Where the benchmark and the repository are, with both on ``sys.path``
(the harness's ``lib`` and ``run``, and the system under test's ``src``),
and a scratch checkout with tiny CPU cells.  (Not a ``conftest.py``: the
repository's own tests import theirs by that name.)"""

import json
import os
import shutil
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
REPO = os.path.dirname(BENCH)
DATA = os.path.join(TESTS, "data")
#: The closed-loop rate, for cells that add it as a new entry.
SERVED_RPS = {"name": "served_rps", "unit": "req/s", "better": "higher",
              "bound": 0.01, "source": "host_clock"}
for _p in (BENCH, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout with the benchmark as it is plus, as new files only, a
    tiny configuration, two tiny mixes and two cells that use them."""
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "chipbench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (bench / "tests" / "data").mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "demo_vae-32.json"),
                bench / "tests" / "data")
    for mix in ("tiny_open", "tiny_scan"):
        shutil.copy(os.path.join(DATA, f"{mix}.json"), bench / "mixes")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "demo_vae-32", "source": "test",
                         "file": "chipbench/tests/data/demo_vae-32.json",
                         "reduced": [], "why": "CPU tests"})
    b["workloads"] += [
        {"name": "demo.open", "config": "demo_vae-32",
         "traffic": "tiny_open", "chips": 1, "why": "CPU tests"},
        {"name": "demo.scan", "config": "demo_vae-32",
         "traffic": "tiny_scan", "chips": 1, "why": "CPU tests"}]
    for m in b["end_to_end"] + b["per_layer"]:
        w = m.get("workloads")
        if w and "sd15-512.zipf" in w:
            w.append("demo.open")
        if w and "sd35-1024.scan" in w:
            w.append("demo.scan")
    if not any("demo.scan" in m.get("workloads", ()) for m in b["end_to_end"]):
        b["end_to_end"].append(SERVED_RPS | {"workloads": ["demo.scan"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return str(root)
