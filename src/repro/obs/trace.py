"""Span/event recorder and the live span helper — the core of ``repro.obs``.

Contract (ROADMAP module map):

* **one span system, two sinks** — :func:`span` opens a
  ``jax.profiler.TraceAnnotation``, which a running profile puts on the
  device trace's clock and which costs about a microsecond without one;
  given a :class:`TraceRecorder`, it also records the span there.
  ``TraceRecorder.timed`` is the same helper.  Spans are always
  emitted, and inert without a profile or a recorder.
* **events outside jit** — instants and counters come from drained
  chunk history and scheduler/cache callbacks, never from inside
  jit-traced code, so a recorder cannot perturb compilation, donation
  or dispatch of the runs it observes; ``obs=None`` runs the identical
  jit programs.
* **virtual + wall clocks** — every recorded event carries a
  virtual-clock timestamp (engine iterations, the serving stack's
  deterministic time base) and a wall-clock timestamp (seconds since
  the recorder's creation).  The Chrome export lays spans out on the
  wall clock and keeps the virtual clock in ``args``.

The event buffer is a bounded ring (``capacity`` events): a runaway
producer overwrites the oldest events and increments ``dropped`` instead
of growing without bound.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Iterator

import jax

from repro.obs.metrics import MetricsRegistry

# Event phases, mirroring the Chrome trace-event vocabulary the export
# layer targets: complete span, instant, counter sample.
PH_SPAN = "X"
PH_INSTANT = "i"
PH_COUNTER = "C"

DEFAULT_CAPACITY = 1 << 16


@dataclasses.dataclass
class TraceEvent:
    """One recorded event.  ``wall``/``wall_dur`` are seconds relative to
    the recorder's creation; ``vt``/``vt_dur`` are virtual-clock units
    (engine iterations).  ``track`` names the timeline the event belongs
    to (a device, a lane, a tenant) — the export layer maps each distinct
    track to its own thread row."""

    name: str
    ph: str
    cat: str
    track: str
    wall: float
    vt: float
    wall_dur: float = 0.0
    vt_dur: float = 0.0
    args: dict[str, Any] = dataclasses.field(default_factory=dict)


class TraceRecorder:
    """Bounded-ring recorder with an attached metrics registry.

    All emission helpers are plain host Python — cheap enough to call
    from drain loops (one call per iteration row, not per vertex), and
    never called from inside traced code.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = int(capacity)
        self.events: collections.deque[TraceEvent] = collections.deque(
            maxlen=self.capacity
        )
        self.dropped = 0
        self.metrics = MetricsRegistry()
        self._wall0 = time.monotonic()

    # -- clocks ----------------------------------------------------------
    def wall(self) -> float:
        """Seconds since the recorder was created (the trace's wall origin)."""
        return time.monotonic() - self._wall0

    def wall_at(self, t_monotonic: float) -> float:
        """Convert a caller-captured ``time.monotonic()`` stamp into the
        trace's wall coordinates (instrumentation sites already take
        these stamps for their own accounting — reuse, don't re-read)."""
        return t_monotonic - self._wall0

    # -- emission --------------------------------------------------------
    def _push(self, ev: TraceEvent) -> None:
        if len(self.events) == self.capacity:
            self.dropped += 1
        self.events.append(ev)

    def span(
        self, name: str, *, cat: str = "host", track: str = "main",
        wall: float, wall_dur: float = 0.0, vt: float = 0.0,
        vt_dur: float = 0.0, **args: Any,
    ) -> None:
        """Record a completed span (explicit start + duration)."""
        self._push(TraceEvent(name, PH_SPAN, cat, track, wall, vt,
                              wall_dur, vt_dur, args))

    def instant(
        self, name: str, *, cat: str = "event", track: str = "main",
        vt: float = 0.0, wall: float | None = None, **args: Any,
    ) -> None:
        """Record an instantaneous event (defaults to 'now' on the wall)."""
        w = self.wall() if wall is None else wall
        self._push(TraceEvent(name, PH_INSTANT, cat, track, w, vt, args=args))

    def counter(
        self, name: str, value: float, *, cat: str = "counter",
        track: str = "main", vt: float = 0.0, wall: float | None = None,
    ) -> None:
        """Record a counter sample (renders as a counter track in Chrome)."""
        w = self.wall() if wall is None else wall
        self._push(TraceEvent(name, PH_COUNTER, cat, track, w, vt,
                              args={"value": float(value)}))

    def timed(self, name: str, **kw: Any):
        """:func:`span` into this recorder."""
        return span(name, self, **kw)

    # -- views -----------------------------------------------------------
    def __len__(self) -> int:
        return len(self.events)


def span(
    name: str, obs: TraceRecorder | None = None, *, cat: str = "host",
    track: str = "main", vt: float = 0.0, **args: Any,
):
    """A live span around the body: a profiler annotation always (the
    ``args`` given here are its stats), and a recorded span when ``obs``
    is given.  With ``obs``, the context
    yields the event it will record, so the body can set ``vt_dur`` and
    ``args`` it learns while the span is open."""
    if obs is None:
        return jax.profiler.TraceAnnotation(name, **args)
    return _recorded_span(
        obs, TraceEvent(name, PH_SPAN, cat, track, 0.0, vt, args=args))


def set_stats(opened, **stats: Any) -> None:
    """Stats a span's body learns while the span is open, on what
    :func:`span` yielded: the recorded event's ``args`` with a recorder,
    else the profiler annotation's metadata."""
    if isinstance(opened, TraceEvent):
        opened.args.update(stats)
    else:
        opened.set_metadata(**stats)


@contextlib.contextmanager
def _recorded_span(obs: TraceRecorder, ev: TraceEvent) -> Iterator[TraceEvent]:
    with jax.profiler.TraceAnnotation(ev.name, **ev.args):
        ev.wall = obs.wall()
        try:
            yield ev
        finally:
            ev.wall_dur = obs.wall() - ev.wall
            obs._push(ev)
