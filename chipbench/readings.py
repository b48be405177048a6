"""What a per-layer reader is given, and how the harness finds a reader
by the metric's name.

A metric ``<base>.<suffix>`` (``decode_step_ms.chat``) is read by
``chipbench/layer_metrics/<base>.py``, whose ``read(reading)`` returns a
number, or None when there is nothing to read; the harness then leaves
the metric out of the result line.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os
from types import ModuleType
from typing import Callable, Dict, List, Optional, Tuple

from chipbench import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Reading:
    dims: object                    # the family's Dims of the configuration
    family: ModuleType              # chipbench/families/<family>.py
    peaks: Dict[str, float]
    max_batch: int                  # slots of one engine
    window_s: float
    steps: List                     # serve.StepRec of the window
    tracked: List                   # serve.Tracked of every request
    counters: Dict[str, float]      # EngineStats deltas over the window
    trace: Optional[Dict] = None    # trace.load form, traced run only
    traced: List = dataclasses.field(default_factory=list)
    # (StepRec, (start_ns, end_ns)) of the steps wholly inside the trace

    def trace_window(self) -> Tuple[float, float]:
        return tr.window(self.trace)

    def traced_span(self) -> Optional[Tuple[float, float]]:
        """From the first traced step's start to the last one's end."""
        if not self.traced:
            return None
        return self.traced[0][1][0], self.traced[-1][1][1]


def reader(name: str) -> Callable[[Reading], Optional[float]]:
    base = name.split(".")[0]
    path = os.path.join(HERE, "layer_metrics", base + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_layer_{base}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
