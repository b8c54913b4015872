"""CPU rehearsal of ``chip_smoke.py``: every phase function, tiny, on the
virtual mesh — so a later change cannot break the smoke test without a
red test here.  The one-chip phases run under a one-device communication
(what the chip gives them), the cross-chip phase on four of the virtual
devices.  The script itself still refuses to run without a TPU.
"""

import json
import os
import sys

import jax
import pytest

import heat_tpu as ht
from heat_tpu.parallel.comm import Communication

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture()
def one_device():
    """The default communication narrowed to one device, as on one chip."""
    ht.use_comm(Communication(jax.devices()[:1]))
    try:
        yield
    finally:
        ht.use_comm(ht.WORLD)


def test_header():
    rec = chip_smoke.phase_header("/some/dir")
    assert rec["platform"] == "cpu" and rec["device_count"] == len(jax.devices())
    json.dumps(rec)


def test_array_core(one_device):
    rec = chip_smoke.phase_array_core(0, "cpu", n=4096, f=16, sort_n=4096)
    assert rec["warm_dispatch_hit_rate"] == 1.0
    json.dumps(rec)


def test_ingest_fit(one_device):
    if not ht.io.supports_hdf5():
        pytest.skip("h5py missing")
    rec = chip_smoke.phase_ingest_fit(0, "cpu", n=4096, f=16, k=8)
    assert rec["centers_max_abs_err"] < 5e-3
    json.dumps(rec)


def test_hsvd(one_device):
    rec = chip_smoke.phase_hsvd(0, "cpu", m=4096, n=128, rank=10)
    # selected by the same gate as on the chip; interpreted, not compiled, here
    assert rec["gram_syrk_selected"] and rec["gram_syrk_compiled_kernel"] is False
    json.dumps(rec)


def test_fft(one_device):
    rec = chip_smoke.phase_fft(0, "cpu", n=32, ref_n=16)
    assert rec["complex64_native"]
    assert os.environ.get("HEAT_TPU_PLANAR") is None  # the route switch was undone
    json.dumps(rec)


def test_training(one_device):
    rec = chip_smoke.phase_training(0, "cpu", batch=32, steps=4, scan_steps=8)
    assert rec["last_loss"] < rec["first_loss"]
    json.dumps(rec)


def test_attention(one_device):
    rec = chip_smoke.phase_attention(0, "cpu", seq=64, heads=4, dim=16, ref_seq=32)
    assert rec["flash_kernel"] is False  # no TPU: "flash" is the einsum path
    json.dumps(rec)


def test_four_chips():
    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices")
    rec = chip_smoke.phase_four_chips(
        0, "cpu", Communication(jax.devices()[:4]), n=4096, f=16, k=8, qr_shape=(1024, 16),
        fft_n=16, sort_n=1 << 16, batch=32, attn_shape=(64, 4, 16),
    )
    assert set(rec["data_parallel"]) == {"implicit", "bucketed", "fused"}
    json.dumps(rec)


def test_a_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check(False, "injected", value=1)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_on("tpu", x=ht.arange(4))  # lives on the CPU here


def test_script_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 2
    assert chip_smoke.main(["--four-chips"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "Refusing to run" in out.err
