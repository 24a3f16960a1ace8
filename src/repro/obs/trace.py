"""Per-request span records and the JSONL event-log sink (DESIGN.md §11).

The span model is the serve engine's request lifecycle::

    submit -> admit -> prefill -> {decode | draft/verify round}* -> finish

Each transition is one EVENT: a flat JSON object with the request id
(``rid``; batch-wide events like decode steps carry ``rid: null``), the
event name, a wall-clock timestamp (``time.perf_counter`` — monotonic,
same clock the latency histograms use), and event-specific attributes
(slot, prompt length, round width, accepted count, ...).  Events are
appended to a JSONL sink as they happen; one line per event keeps the
log greppable, streamable, and writable without buffering a run in
memory.

Tracing is separate from metrics on purpose: histograms answer "what is
p99 ITL", the event log answers "what happened to request 17" — and the
event log has per-event cost (a dict build + a file write), so it stays
opt-in while the metrics registry can run always-on.

:class:`ProfileHook` is the optional deep-dive: capture a
``jax.profiler`` trace around the first N decode dispatches of a run,
so a slow step found in the histograms can be cross-examined at the
XLA level without instrumenting anything by hand. :func:`span` puts the
engine's own host work into that trace (``serve.step``, ``serve.admit``,
``serve.decode``), beside the device ops it dispatches.
"""
from __future__ import annotations

import json
import time
from typing import Any, IO

__all__ = ["TraceLog", "ProfileHook", "span"]


def span(name: str, **attrs: Any):
    """A host span in the ``jax.profiler`` trace, on the device trace's
    clock: a ``TraceAnnotation`` carrying ``attrs``. It records only
    while a profiler captures (a check of a flag otherwise), so callers
    open it unconditionally."""
    import jax

    return jax.profiler.TraceAnnotation(name, **attrs)


class TraceLog:
    """Append-only JSONL event sink.

    ``sink`` is a path (opened for append; the common case), a
    file-like object (e.g. ``io.StringIO`` in tests), or ``None`` to
    buffer events in memory (``.events`` — handy for assertions).
    """

    def __init__(self, sink: str | IO[str] | None = None):
        self.events: list[dict] = []
        self._own = False
        self._fh: IO[str] | None = None
        if isinstance(sink, str):
            self._fh = open(sink, "a")
            self._own = True
        elif sink is not None:
            self._fh = sink
        self._t0 = time.perf_counter()

    def event(self, name: str, rid: int | None = None, **attrs: Any) -> dict:
        """Record one event; returns the event dict (already sunk)."""
        ev = {"t": time.perf_counter() - self._t0, "event": name, "rid": rid, **attrs}
        if self._fh is not None:
            self._fh.write(json.dumps(ev, sort_keys=True) + "\n")
        else:
            self.events.append(ev)
        return ev

    def flush(self) -> None:
        if self._fh is not None:
            self._fh.flush()

    def close(self) -> None:
        self.flush()
        if self._own and self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self) -> "TraceLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class ProfileHook:
    """Capture a ``jax.profiler`` trace around N decode dispatches.

    The engine calls :meth:`step` once per decode/round dispatch; the
    hook starts the profiler on the first call and stops it after
    ``n_steps`` — bounding the trace to a representative window instead
    of an entire serve run (profiler traces grow fast).  Inert after
    the window closes; safe to keep calling.
    """

    def __init__(self, log_dir: str, n_steps: int = 20):
        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        self.log_dir = log_dir
        self.n_steps = int(n_steps)
        self.seen = 0
        self.active = False
        self.done = False

    def step(self) -> None:
        if self.done:
            return
        if not self.active:
            import jax

            jax.profiler.start_trace(self.log_dir)
            self.active = True
        self.seen += 1
        if self.seen >= self.n_steps:
            self.stop()

    def stop(self) -> None:
        """Stop the capture early (idempotent; also the end-of-run hook)."""
        if self.active:
            import jax

            jax.profiler.stop_trace()
            self.active = False
        self.done = True
