"""One process for each chip, without importing JAX.

A TPU chip belongs to one process at a time, and a process that starts
JAX takes every chip it can see. The parents that start several model
processes (`serve --workers`, `router --spawn`, `serve_bench --fleet`,
`chip_smoke.py --chips 4`) never import JAX themselves, so what they can
do is set the TPU runtime's own environment for each child.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Mapping, Optional


def local_chip_count() -> int:
    """TPU chips on this host, from their device files (0 on a host with
    none): /dev/vfio/<n> on v5e and later, /dev/accel<n> before."""
    return (len(glob.glob("/dev/vfio/[0-9]*"))
            or len(glob.glob("/dev/accel[0-9]*")))


def one_chip_env(chip: int,
                 env: Optional[Mapping[str, str]] = None) -> Dict[str, str]:
    """``env`` (default os.environ) with the child held to chip ``chip``:
    it sees that one chip as a 1x1x1 topology of its own. Harmless where
    no TPU runtime loads."""
    out = dict(os.environ if env is None else env)
    out.update(TPU_VISIBLE_CHIPS=str(chip),
               TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
               TPU_PROCESS_BOUNDS="1,1,1")
    return out


def refuse_more_than_chips(n: int, label: str) -> None:
    """Exit with one line when ``n`` model processes are asked for on a
    TPU host with fewer chips: the extra children could not get one."""
    chips = local_chip_count()
    if chips and n > chips:
        raise SystemExit(f"{label}: {n} model processes on a host with "
                         f"{chips} TPU chip(s) — a chip belongs to one "
                         "process at a time")
