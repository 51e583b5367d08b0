"""The device a run measures, and its published peaks.

A measurement runs on the accelerator it names or not at all: no CPU
fallback, and a chip whose ``device_kind`` is not in ``peaks.json`` is an
error, never a default.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Sequence

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoAccelerator(SystemExit):
    """Raised before any work when the run cannot be measured here."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}; nothing was run")


def require_chips(devices: Sequence, chips: int) -> List:
    """The first ``chips`` TPU devices, or :class:`NoAccelerator`."""
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "none"
        raise NoAccelerator(f"no TPU (JAX found {plat!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devices)}")
    peaks(devices[0].device_kind)        # unknown chips fail here too
    return list(devices[:chips])


def peaks(device_kind: str, path: Path = PEAKS) -> Dict:
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise NoAccelerator(f"no published peaks for device kind "
                            f"{device_kind!r} in {Path(path).name} "
                            f"(have {sorted(table)})")
    return table[device_kind]
