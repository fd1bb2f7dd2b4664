"""Whole runs at the demo decoder's size on the CPU (the harness's look
for a chip skipped): a sound run is correct; the control (the program's
bf16 weight path) and each fault planted under the timed path are not.
And a run that finds no TPU exits non-zero and prints nothing."""

import json

import pytest

from chipbench_paths import REPO, tiny_root  # noqa: F401 (a fixture)

import run as R
from lib.cells import load_cell

SEED = 2 ** 35 + 17


def _run(root, workload, seconds, **kw):
    args = R.parse(["--workload", workload, "--seed", str(SEED),
                    "--seconds", str(seconds)])
    return R.run(args, load_cell(workload, root), require_tpu=False,
                 compile_cache=False, **kw)


def test_refuses_a_cpu(capsys):
    rc = R.main(["--workload", "sd35-1024.zipf", "--seed", str(SEED),
                 "--seconds", "1"], root=REPO)
    assert rc != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("workload", ["demo.open", "demo.scan"])
def test_sound_run_is_correct(tiny_root, workload):
    res = _run(tiny_root, workload, 0.5)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    names = set(res["metrics"])
    assert "setup_s" in names
    assert names & {"read_p90_ms", "read_p50_ms", "served_rps"}
    # the decode executables' temporaries are counted
    assert res["device"]["memory_peak_bytes"] > 0
    json.dumps(res)


def test_control_is_not_correct(tiny_root):
    res = _run(tiny_root, "demo.scan", 0.5, precision="bfloat16")
    assert res["correct"] is False
    assert res["checks"]["mismatch_ppm"]["value"] > \
        res["checks"]["mismatch_ppm"]["limit"]


def _alter_answer(vae, box):
    """Every decoded image comes out with its low bits flipped."""
    import jax.numpy as jnp
    orig = vae.decode_u8
    vae.decode_u8 = lambda z, precision=None: jnp.bitwise_xor(
        orig(z, precision), 2)


def _drop_half_batch(vae, box):
    """The second half of every batch gets the first image's pixels."""
    orig = vae.decode_u8

    def decode(z, precision=None):
        y = orig(z, precision)
        n = y.shape[0]
        return y if n < 2 else y.at[n // 2:].set(y[0])
    vae.decode_u8 = decode


def _swap_answers(vae, box):
    """Answers of one call come back rotated by one request."""
    orig = box.get_many

    def get_many(oids, timestamps_ms=None):
        res = orig(oids, timestamps_ms)
        payloads = [r.payload for r in res]
        for r, p in zip(res, payloads[1:] + payloads[:1]):
            r.payload = p
        return res
    box.get_many = get_many


@pytest.mark.parametrize("fault", [_alter_answer, _drop_half_batch,
                                   _swap_answers])
def test_fault_is_not_correct(tiny_root, fault):
    res = _run(tiny_root, "demo.scan", 0.5, patch=fault)
    assert res["correct"] is False, res["checks"]
