"""Result of a training/tuning run (ray: python/ray/air/result.py)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from ray_tpu.air.checkpoint import Checkpoint


@dataclasses.dataclass
class Result:
    metrics: Optional[Dict[str, Any]]
    checkpoint: Optional[Checkpoint]
    error: Optional[Exception] = None
    metrics_history: Optional[List[Dict[str, Any]]] = None
    # What a train run did outside its steady step (train/run_record.py):
    # lifecycle spans under one trace id, stalled steps, report delivery.
    # Set by `DataParallelTrainer.fit`, whether tracing is on or not.
    run_record: Optional[Dict[str, Any]] = None

    @property
    def config(self) -> Optional[Dict[str, Any]]:
        return (self.metrics or {}).get("config")
