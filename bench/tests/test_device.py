"""The harness refuses to measure anything but the TPU the cell asks for:
a CPU, too few chips, or a chip with no published peaks."""

import json
from types import SimpleNamespace as NS

import pytest

from bench import device, run


def chip(kind="TPU v5 lite", platform="tpu"):
    return NS(platform=platform, device_kind=kind)


def test_refuses_the_cpu():
    import jax
    with pytest.raises(SystemExit) as e:
        device.require_chips(jax.devices(), 1)
    assert e.value.code not in (0, None)


def test_refuses_too_few_chips():
    with pytest.raises(device.NoAccelerator):
        device.require_chips([chip()], 4)
    assert len(device.require_chips([chip()] * 4, 4)) == 4


def test_refuses_an_unknown_device_kind():
    with pytest.raises(device.NoAccelerator, match="no published peaks"):
        device.require_chips([chip("TPU v99 imaginary")], 1)
    with pytest.raises(device.NoAccelerator):
        device.peaks("TPU v99 imaginary")


def test_peaks_are_the_published_v5e_ones():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "Google Cloud" in p["source"]


def test_run_exits_nonzero_before_any_work(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "minicpm-2b.chat-swap", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    for line in capsys.readouterr().out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
