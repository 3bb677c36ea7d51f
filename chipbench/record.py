"""What a run hands its per-layer metric readers.

A reader is ``read(record) -> float | None``: it returns None when the run
gave it nothing to read, and the harness then leaves the metric out.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

from chipbench.trace import Summary


@dataclass
class Record:
    cell: dict
    window_s: float                      # length of the measured window
    answered: int                        # answers that came in it
    compiles: int                        # programs compiled or loaded in it
    counters: Dict[str, float]           # ServerStats scalars, window delta
    chunk_corpora: float                 # corpora summed over its chunks
    stages: Dict[str, Tuple[int, float]] = field(default_factory=dict)
    trace: Optional[Summary] = None      # the profiler's, when traced

    def stage_mean_ms(self, stage: str) -> Optional[float]:
        """Mean of one ``repro_server_stage_seconds`` stage over the
        window, in ms."""
        count, seconds = self.stages.get(stage, (0, 0.0))
        return 1e3 * seconds / count if count else None

    @property
    def chunks(self) -> float:
        return self.counters.get("batched_calls", 0) \
            + self.counters.get("single_calls", 0)
