"""One process for each chip.

A TPU chip belongs to one process at a time: the first process to initialise
JAX opens every chip it can see, and a second one then fails or hangs. The
launchers (``pathway_tpu spawn``, ``resilience.Supervisor``) start N children
from one environment, so unless the platform is forced to the CPU each child
must be told which chip is its own before it imports JAX. Nothing here imports
JAX: a launcher that did would hold the chips its children need.
"""

from __future__ import annotations

import glob
import os
from typing import Mapping


def host_chips(env: Mapping[str, str]) -> list[str]:
    """Ids of the TPU chips a child of this environment may be given: the
    parent's own ``TPU_VISIBLE_CHIPS`` if it was narrowed, else one id per
    accelerator device node of the host."""
    visible = env.get("TPU_VISIBLE_CHIPS", "").strip()
    if visible:
        return [c.strip() for c in visible.split(",") if c.strip()]
    nodes = glob.glob("/dev/accel[0-9]*") or [
        p for p in glob.glob("/dev/vfio/[0-9]*") if os.path.basename(p).isdigit()
    ]
    return [str(i) for i in range(len(nodes))]


def child_chip_env(
    env: Mapping[str, str], process_id: int, processes: int
) -> dict[str, str]:
    """The entries that give child ``process_id`` of ``processes`` a chip of
    its own. Empty for a single process (it may drive every chip of the host)
    and when ``JAX_PLATFORMS=cpu`` holds the children to the CPU. Raises
    ``ValueError`` when there are more processes than chips — for every
    ``process_id`` alike, so a launcher that builds all environments first
    fails before it has spawned anything."""
    if processes <= 1 or env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return {}
    chips = host_chips(env)
    if processes > len(chips):
        raise ValueError(
            f"{processes} processes need {processes} TPU chips, one each, and "
            f"this host has {len(chips)}: lower --processes (threads share a "
            "process and its chips), or set JAX_PLATFORMS=cpu to run the "
            "processes on the CPU"
        )
    return {
        "TPU_VISIBLE_CHIPS": chips[process_id],
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
