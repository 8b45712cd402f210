"""Job kind ``epoch``: repeated all-node epochs through
``Session.infer_all``.  The Session caches its result, so the cached
embeddings are dropped before each epoch.  Traffic: ``{"job": "epoch"}``.

The comparison gets the last timed epoch's final embeddings (level L of
every node), the sampled layer graphs with the edge list they must
agree with, and the features the epoch started from.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

UNIT = "epoch"


class Job:
    def __init__(self, session, traffic: Dict, seed: int, spans):
        self.s = session
        self.spans = spans
        self.H: Optional[np.ndarray] = None
        self.counters: Dict[str, List[float]] = {}

    def _epoch(self) -> np.ndarray:
        with self.spans("reset"):
            self.s._H = None
        with self.spans("epoch"):
            return self.s.infer_all()

    def warm(self, compiles) -> List[str]:
        self._epoch()
        return [f"warm-up epoch: {compiles.count} compiles"]

    def step(self) -> None:
        self.H = self._epoch()

    def outputs(self) -> Dict:
        s = self.s
        return {"levels": {s.cfg.model.n_layers: self.H},
                "graphs": [(lg.nbr, lg.mask) for lg in s.layer_graphs],
                "X": s.X, "src": s.src, "dst": s.dst}
