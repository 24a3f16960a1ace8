"""What one run measured, as the metric readers see it."""
from __future__ import annotations

import dataclasses
import math

from harness.tracing import Summary


@dataclasses.dataclass
class LmRecord:
    """One request of the window, on the host clock (``perf_counter``)."""

    due: float
    submitted: float
    max_new: int
    prompt_len: int
    token_times: list[float] = dataclasses.field(default_factory=list)
    admitted: float | None = None  # from the engine's own admit event, traced runs only
    done: bool = False
    gave_up: float | None = None  # when the run stopped waiting for it

    @property
    def ttft_s(self) -> float:
        if self.token_times:
            return self.token_times[0] - self.due
        return self.gave_up - self.due

    def gaps_s(self) -> list[float]:
        t = self.token_times
        gaps = [b - a for a, b in zip(t, t[1:])]
        if not self.done and self.gave_up is not None:
            gaps.append(self.gave_up - (t[-1] if t else self.due))
        return gaps


@dataclasses.dataclass
class Run:
    config: dict
    cell: dict
    peaks: dict
    seed: int
    seconds: float
    traced: bool
    t_start: float = 0.0  # process start, perf_counter
    setup_s: float = math.nan
    window_s: float = math.nan  # host clock, first dispatch to the last result on the host
    attempted: int = 0
    failed: int = 0
    tokens: int = 0  # LM: output tokens completed in the window
    images: int = 0  # CNN: images whose logits reached the host in the window
    calls: int = 0
    requests: list[LmRecord] = dataclasses.field(default_factory=list)
    rids: list[int] = dataclasses.field(default_factory=list)  # the engine's id of each request
    trace: Summary | None = None
    memory_peak: int | None = None
    check_result: dict = dataclasses.field(default_factory=dict)
    compared: dict = dataclasses.field(default_factory=dict)  # name -> {"value", "limit"}

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(
            math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in self.compared.values()
        )

    def note(self, line: str) -> None:
        print(line, flush=True)
