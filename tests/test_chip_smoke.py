"""chip_smoke.py rehearsed on virtual CPU devices at tiny sizes.

The phases are the script's own functions, run with interpret-mode
kernels on meshes cut from the 8 host devices; `main()` itself must
refuse a machine without a TPU.
"""
import json
import pathlib
import sys

import pytest

from repro.configs.dlrm import DLRMConfig
from repro.core.topology import make_mesh

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

TINY = DLRMConfig(n_tables=4, emb_dim=32, rows_per_table=500,
                  fc_dims=(64, 32, 16))


def test_kernel_phase_interpreted():
    chip_smoke.phase_kernels(interpret=True, n_elems=300 * 256,
                             mm_shape=(16, 200, 130), tables=3, rows=300,
                             batch=200)


def test_dlrm_phase_one_device():
    chip_smoke.phase_dlrm(TINY, make_mesh((1, 1, 1), ("pod", "data",
                                                      "model")),
                          batches=2, batch_size=8)


def test_dlrm_phase_sharded_four_devices():
    chip_smoke.phase_dlrm_sharded(
        TINY, make_mesh((1, 1, 4), ("pod", "data", "model")), batches=2,
        batch_size=8)


def test_collective_phase_four_devices():
    chip_smoke.phase_collectives(make_mesh((4,), ("x",)), sizes=(256, 4096))


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_cpu(capsys, argv):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main(argv)
    assert e.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    assert not any(json.loads(line).get("ok") for line in out.splitlines()
                   if line.startswith("{"))
