"""``chip_smoke.py`` rehearsed on the CPU at the demo decoder's size: the
same phases the chip runs (puts by recipe, demotion, windows plus a
stream, every hit class, no compile inside the serving window, pixels
against the fp32 reference), the four-shard phase on four virtual CPU
devices, and the refusal to run without a TPU."""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_one_chip_phases_at_demo_size(smoke, tmp_path):
    out = smoke.one_chip(str(tmp_path), decoder="demo", res=32)
    assert all(n > 0 for n in out["hit_classes"].values())
    assert out["window_compiles"] == 0 < out["executables_built"]
    assert out["max_lsb_vs_reference"] <= 1
    assert set(out["setup_s"]["prewarm"]) == set(smoke.BUCKETS)


def test_four_shard_phase_on_virtual_devices():
    """Each shard on its own device, classification and pixels equal to
    the one-shard box (run in a child: the device count is fixed when the
    CPU backend starts)."""
    code = textwrap.dedent(f"""
        import importlib.util, json
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {os.path.join(ROOT, "chip_smoke.py")!r})
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        print(json.dumps(m.four_chips(decoder="demo", res=32)))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["max_lsb"] <= 1
    assert len(res["decodes_per_shard"]) == 4


def test_shard_device_slots_wrap_over_devices():
    from repro.store.sharding import shard_device
    devs = ["d0", "d1", "d2", "d3"]
    names = [(f"node{2 * s}", f"node{2 * s + 1}") for s in range(6)]
    assert [shard_device(n, devs) for n in names] == \
        ["d0", "d1", "d2", "d3", "d0", "d1"]
    assert shard_device(("node0", "node1", "node2"), devs[:1]) == "d0"
