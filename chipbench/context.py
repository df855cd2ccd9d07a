"""What a metric's reader sees of a run (``metrics/<name>.py`` defines
``read(run) -> float | None``; ``None`` leaves the metric out)."""
from __future__ import annotations

import dataclasses
from typing import Iterable, Optional

from chipbench import counts


@dataclasses.dataclass
class Run:
    config: dict
    ref: object                 # the configuration's reference module
    peak: dict                  # counts.peaks(device_kind)
    window: object              # drive.Window
    setup_s: float
    trace: Optional[object] = None   # xplane.Reduced of the profiled stretch
    _work: dict = dataclasses.field(default_factory=dict)

    @property
    def precision(self) -> str:
        return self.config["precision"]

    def phase(self, name: str) -> list:
        """The program's own span durations (ms) of one engine phase, as
        recorded inside the window."""
        return self.window.phases.get(name, [])

    def counter(self, *path) -> float:
        """Growth of one engine stats counter over the window."""
        a, b = self.window.stats0, self.window.stats1
        for k in path:
            a, b = a[k], b[k]
        return b - a

    def work(self, tickets: Iterable[int]) -> counts.Work:
        """Summed work of the scenes the tickets carried (counted once per
        distinct scene content, then cached)."""
        total = counts.Work()
        for t in tickets:
            scene = self.window.requests[t].scene
            w = self._work.get(id(scene))
            if w is None:
                w = self._work[id(scene)] = counts.scene_work(
                    self.ref, self.config["model"], scene.coords,
                    self.precision, self.peak)
            total += w
        return total
