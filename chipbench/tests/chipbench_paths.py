"""Where the benchmark and the repository are, with both on ``sys.path``
(the harness's ``lib`` and ``run``, and the system under test's ``src``),
and scratch checkouts with tiny CPU cells.  (Not a ``conftest.py``: the
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


#: Appended to a copy of ``references/autoencoderkl.py``: a decoder family
#: that exists only in a test's checkout.  Each of its family names notes
#: in ``<module>.seen`` that the harness read it, and ``PROGRAM_KEYS``
#: checks the program's latent channels against a key that only this
#: family's configuration states.
FAMILY_SENTINELS = """

import os as _os


def _seen(name):
    with open(_os.path.splitext(__file__)[0] + ".seen", "a") as f:
        f.write(name + "\\n")


PROGRAM_KEYS = dict(PROGRAM_KEYS, latent_channels="sentinel_latent_channels")
_kl = (latent_hwc, weight_tree, calls)


def latent_hwc(arch):
    _seen("latent_hwc")
    return _kl[0](arch)


def weight_tree(arch, init, normal):
    _seen("weight_tree")
    return _kl[1](arch, init, normal)


def calls(arch):
    _seen("calls")
    return _kl[2](arch)
"""


def family_root(root, sentinel_latent_channels: int) -> str:
    """A checkout with the benchmark as it is plus, as new files only, the
    decoder family ``kl_copy`` (:data:`FAMILY_SENTINELS`), a configuration
    ``copy_vae-32`` that names it (the demo decoder's, with
    ``sentinel_latent_channels`` stated), a tiny scan mix and a cell
    ``copy.scan`` that uses them."""
    root = str(root)
    bench = os.path.join(root, "chipbench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    refs = os.path.join(bench, "references")
    with open(os.path.join(refs, "autoencoderkl.py")) as f:
        source = f.read()
    with open(os.path.join(refs, "kl_copy.py"), "w") as f:
        f.write(source + FAMILY_SENTINELS)
    with open(os.path.join(DATA, "demo_vae-32.json")) as f:
        cfg = json.load(f)
    cfg["name"], cfg["reference"] = "copy_vae-32", "kl_copy"
    cfg["architecture"]["sentinel_latent_channels"] = sentinel_latent_channels
    os.makedirs(os.path.join(bench, "configs"), exist_ok=True)
    with open(os.path.join(bench, "configs", "copy_vae-32.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(DATA, "tiny_scan.json"),
                os.path.join(bench, "mixes"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "copy_vae-32", "source": "test",
                         "file": "chipbench/configs/copy_vae-32.json",
                         "reduced": [], "why": "CPU tests"})
    b["workloads"].append({"name": "copy.scan", "config": "copy_vae-32",
                           "traffic": "tiny_scan", "chips": 1,
                           "why": "CPU tests"})
    for m in b["end_to_end"] + b["per_layer"]:
        w = m.get("workloads")
        if w and "sd35-1024.scan" in w:
            w.append("copy.scan")
    if not any("copy.scan" in m.get("workloads", ()) for m in b["end_to_end"]):
        b["end_to_end"].append(SERVED_RPS | {"workloads": ["copy.scan"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return root
