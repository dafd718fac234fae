"""Which process owns which TPU chip.

A chip belongs to one process at a time: libtpu refuses a second opener
("The TPU is already in use by process with pid N"). So the runtime
never opens a chip itself — ``ray_tpu.init()`` counts chips from the
device nodes, without initialising a JAX backend — and hands each chip
to exactly one worker process: the one spawned for the actor or task
that holds the ``TPU`` resource. That process is started without the
CPU pin and with libtpu's visible-chip variables naming the chips it
was granted; every other worker stays pinned to the CPU.
"""

from __future__ import annotations

import glob
import os
import threading
from typing import Dict, Mapping, Optional, Sequence, Tuple


def detect_num_chips() -> int:
    """Chips on this host, counted from the device nodes (``/dev/accel*``
    on older TPU VMs, ``/dev/vfio/<n>`` on v5e and later). Touches no
    JAX backend, so the chips stay free for the processes that need
    them."""
    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    try:
        return sum(1 for n in os.listdir("/dev/vfio") if n.isdigit())
    except OSError:
        return 0


def whole_chips(demand: Mapping[str, float]) -> int:
    """The ``TPU`` amount of a resource demand as a chip count. A chip
    cannot be split between processes, so a fraction is an error."""
    amount = float(demand.get("TPU", 0.0))
    if amount != int(amount):
        raise ValueError(
            f"TPU demand {amount} is not a whole number of chips; a chip "
            f"belongs to one process at a time")
    return int(amount)


def chips_requested(opts: Mapping) -> int:
    """Whole chips an actor's options ask for: ``num_tpus`` (``num_gpus``
    is its alias, as for tasks) or a ``TPU`` entry of ``resources``."""
    amount = opts.get("num_tpus", opts.get("num_gpus"))
    if not amount:
        amount = (opts.get("resources") or {}).get("TPU", 0.0)
    return whole_chips({"TPU": amount})


def worker_env(chips: Sequence[int], total: int) -> Dict[str, str]:
    """Environment a worker process granted ``chips`` of this host's
    ``total`` is started with.

    No chips: pinned to the CPU. Chips: the TPU is the default backend
    and failing to open it is an error, never a fall to the CPU. One
    chip of several is shown through libtpu's visible-chip variables (the
    process then sees it as its device 0); every chip of the host needs
    none. Found on the v5e 2x2 host with libtpu 0.0.34: four one-chip
    processes run side by side this way; a two-chip view (bounds 1,2,1)
    was refused, so it is not offered."""
    if not chips:
        return {"JAX_PLATFORMS": "cpu"}
    env = {"JAX_PLATFORMS": "tpu,cpu"}
    if len(chips) >= total:
        return env
    if len(chips) != 1:
        raise ValueError(
            f"a process can be given one chip or all {total} of this "
            f"host, not {len(chips)}")
    env.update(TPU_VISIBLE_CHIPS=str(chips[0]),
               TPU_CHIPS_PER_HOST_BOUNDS="1,1,1", TPU_HOST_BOUNDS="1,1,1")
    return env


class ChipsBusyError(RuntimeError):
    """A TPU claim that cannot be met now; names the current holders."""


class ChipTable:
    """The host's chips and who holds each. Counts are also reserved in
    the runtime's ``ResourcePool`` (``TPU``); this table adds the chip
    ids a process must be shown and the holder's name for errors."""

    def __init__(self, num_chips: int):
        self.total = int(num_chips)
        self._holder: Dict[int, Optional[str]] = {
            i: None for i in range(self.total)}
        self._lock = threading.Lock()

    def busy(self, n: int, holder: str) -> ChipsBusyError:
        """The error for a claim of ``n`` chips that cannot be met."""
        with self._lock:
            held = ", ".join(f"chip {c}: {h}"
                             for c, h in self._holder.items() if h)
        return ChipsBusyError(
            f"{holder} needs {n} TPU chip(s) of this runtime's "
            f"{self.total}; held by — {held or 'nobody'}")

    def take(self, n: int, holder: str) -> Tuple[int, ...]:
        with self._lock:
            free = [c for c, h in self._holder.items() if h is None]
            if len(free) >= n:
                chips = tuple(free[:n])
                for c in chips:
                    self._holder[c] = holder
                return chips
        raise self.busy(n, holder)

    def give_back(self, chips: Sequence[int]) -> None:
        with self._lock:
            for c in chips:
                self._holder[c] = None

    def holders(self) -> Dict[int, Optional[str]]:
        with self._lock:
            return dict(self._holder)
