"""Train/AIR config dataclasses (reference role: ray/air/config.py)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional


@dataclasses.dataclass
class ScalingConfig:
    num_workers: int = 1
    # One whole TPU chip for each worker (reference: use_gpu): the worker
    # process is then the one that opens it. For another count, put
    # ``"TPU": n`` in ``resources_per_worker`` instead.
    use_tpu: bool = False
    resources_per_worker: Optional[Dict[str, float]] = None

    def worker_resources(self) -> Dict[str, float]:
        resources = dict(self.resources_per_worker or {})
        if self.use_tpu:
            resources.setdefault("TPU", 1.0)
        return resources

    @property
    def total_workers(self) -> int:
        return max(int(self.num_workers), 1)


@dataclasses.dataclass
class FailureConfig:
    max_failures: int = 0  # restarts of the whole worker group


@dataclasses.dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_frequency: int = 0
    # Persist checkpoints on a background upload thread so the trainer's
    # report-drain loop (and therefore the training step cadence) never
    # blocks on storage IO; drained once at fit() end.
    async_save: bool = False


@dataclasses.dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: FailureConfig = dataclasses.field(
        default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = dataclasses.field(
        default_factory=CheckpointConfig)
    # Result-stream hooks (train/callbacks.py) — the AIR integrations row.
    callbacks: list = dataclasses.field(default_factory=list)
