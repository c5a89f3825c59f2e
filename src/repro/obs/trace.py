"""Cross-stack span tracing in Chrome ``trace_event`` format.

The paper's mechanism — idle nodes donating watts to lagging nodes
across synchronization points — is a *timeline* phenomenon, and so is
everything the production stack layers on top of it (bucket batching,
async dispatch, cluster admission).  This module is the one tracer all
of those layers report through: spans, instants and counters collected
into a single JSON array that Chrome's ``about:tracing`` and
`Perfetto <https://ui.perfetto.dev>`_ open directly.

Design constraints, in order:

1. **Near-zero cost when disabled.**  Instrumentation sites call the
   *module-level* helpers (:func:`span`, :func:`instant`,
   :func:`counter`, :func:`complete`); each starts with a single
   ``if _TRACER is None`` check and returns a shared singleton — no
   allocation, no string formatting, no lock.  Hot loops that want to
   skip even that check can hoist :func:`get` once.
2. **Thread-safe when enabled.**  Every stage of the streaming service
   (feeder / scheduler / dispatcher / collector) and the engine's
   pipeline emit concurrently; the tracer appends under one lock.
3. **One merged trace across clock domains.**  Wall-clock events
   (service requests, bucket dispatches) use the tracer's monotonic
   epoch; *simulated-time* events (the cluster DES, power timelines)
   pass an explicit ``ts`` in simulated seconds and land on their own
   process tracks, so both views coexist in one file.

Enabling: inject a :class:`Tracer` with :func:`install`, or set
``REPRO_TRACE=<path>`` in the environment before the process starts —
the tracer is installed on first import and the file written at exit
(see :func:`configure_from_env`).

**The profiler's clock.**  :func:`region` marks a point of host work
(one bucket's build, pack, dispatch, wait...) as a
``jax.profiler.TraceAnnotation`` named ``repro.<track>.<name>`` while
JAX is loaded, so a ``jax.profiler`` trace shows it on the host lines
beside the device's operations; with a :class:`Tracer` installed it
also emits the call site's Chrome event.  This module never imports
JAX itself.

Example::

    >>> from repro.obs import trace
    >>> t = trace.install(trace.Tracer())
    >>> with trace.span("plan", cat="sweep", track="engine"):
    ...     trace.instant("bucket-open", track="engine")
    >>> trace.uninstall() is t
    True
    >>> [e["ph"] for e in t.events() if e["ph"] != "M"]
    ['i', 'X']
    >>> sorted(t.events()[-1]) == ["args", "cat", "dur", "name",
    ...                            "ph", "pid", "tid", "ts"]
    True
"""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

#: Environment variable naming the trace output path.  Set it and every
#: instrumented layer of one process run lands in a single Chrome
#: trace, written at interpreter exit (and on :func:`flush_env_trace`).
TRACE_ENV = "REPRO_TRACE"

#: The process-wide tracer, or ``None`` when tracing is disabled.  The
#: module-level emit helpers read it once per call — the whole cost of
#: disabled instrumentation is that read plus a ``None`` check.
_TRACER: Optional["Tracer"] = None


class _NoopSpan:
    """The shared do-nothing context manager the disabled path returns
    (one singleton for the whole process: disabled spans allocate
    nothing per call)."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def note(self, **args) -> None:
        """What :meth:`_Region.note` does when nothing records."""


_NOOP_SPAN = _NoopSpan()


class _Span:
    """An open span: records its start at ``__enter__`` and emits ONE
    complete (``ph: X``) event at ``__exit__`` — half the events of a
    B/E pair and trivially well-nested."""

    __slots__ = ("_tracer", "_name", "_cat", "_track", "_lane", "_args",
                 "_t0")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 track: Optional[str], lane: Optional[str],
                 args: Optional[dict]):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._track = track
        self._lane = lane
        self._args = args

    def __enter__(self) -> "_Span":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self._tracer.complete(self._name, self._t0,
                              time.perf_counter() - self._t0,
                              cat=self._cat, track=self._track,
                              lane=self._lane, args=self._args)
        return False


class _Region:
    """An open :func:`region`: a profiler annotation, a Chrome span, or
    both, entered and left together."""

    __slots__ = ("_annotation", "_span")

    def __init__(self, annotation, span: Optional[_Span]):
        self._annotation = annotation
        self._span = span

    def __enter__(self) -> "_Region":
        if self._annotation is not None:
            self._annotation.__enter__()
        if self._span is not None:
            self._span.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        if self._span is not None:
            self._span.__exit__(*exc)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False

    def note(self, **args) -> None:
        """Add ``args`` to the Chrome event, for what is known only at
        the end of the region (the profiler's args are fixed at entry)."""
        if self._span is not None:
            self._span._args.update(args)


class Tracer:
    """Thread-safe in-memory collector of Chrome ``trace_event`` dicts.

    **Tracks.**  Chrome traces group events by integer ``pid``
    (rendered as a process group) and ``tid`` (a lane inside it).  The
    tracer maps string names to stable small integers — ``track`` is
    the process-level group (``"service"``, ``"engine"``,
    ``"cluster"``, ``"power:<scenario>"``...), ``lane`` the row within
    it (a node, a bucket, a worker thread; defaults to the calling
    thread's name) — and emits the ``process_name`` /
    ``thread_name`` metadata events viewers use for labels.  Distinct
    names never share an id, so merged multi-layer traces cannot
    collide.

    **Clocks.**  Wall-clock events are stamped relative to the
    tracer's creation from ``time.perf_counter()``; simulated-time
    emitters pass ``ts=<seconds>`` explicitly.  Both are exported in
    the format's microseconds.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._epoch = time.perf_counter()
        self._pids: Dict[str, int] = {}
        self._tids: Dict[Tuple[int, str], int] = {}

    # ------------------------------------------------------------ tracks
    def _pid(self, track: Optional[str]) -> int:
        """The stable integer id of one process-level track (allocates
        and emits the ``process_name`` metadata on first use).  Callers
        hold the lock."""
        name = track or "main"
        pid = self._pids.get(name)
        if pid is None:
            pid = len(self._pids) + 1
            self._pids[name] = pid
            self._events.append({
                "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                "args": {"name": name}})
        return pid

    def _tid(self, pid: int, lane: Optional[str]) -> int:
        """The stable integer id of one lane within a track (callers
        hold the lock)."""
        name = lane if lane is not None \
            else threading.current_thread().name
        tid = self._tids.get((pid, name))
        if tid is None:
            tid = sum(1 for p, _ in self._tids if p == pid) + 1
            self._tids[(pid, name)] = tid
            self._events.append({
                "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                "args": {"name": name}})
        return tid

    def track_ids(self) -> Dict[str, int]:
        """Snapshot of the ``track name -> pid`` map (tests assert the
        merged layers stay on disjoint ids)."""
        with self._lock:
            return dict(self._pids)

    # ------------------------------------------------------------- emit
    def _emit(self, ph: str, name: str, ts_us: float, cat: str,
              track: Optional[str], lane: Optional[str],
              args: Optional[dict], **extra) -> None:
        ev = {"ph": ph, "name": name, "cat": cat or "repro",
              "ts": ts_us, "args": args or {}}
        ev.update(extra)
        with self._lock:
            pid = self._pid(track)
            ev["pid"] = pid
            ev["tid"] = self._tid(pid, lane)
            self._events.append(ev)

    def _ts_us(self, ts: Optional[float], t0: Optional[float]) -> float:
        """Resolve a timestamp to trace microseconds: explicit ``ts``
        is simulated seconds; ``t0`` is a ``perf_counter`` reading;
        neither means "now"."""
        if ts is not None:
            return float(ts) * 1e6
        if t0 is None:
            t0 = time.perf_counter()
        return (t0 - self._epoch) * 1e6

    # ------------------------------------------------------------ events
    def span(self, name: str, cat: str = "", track: Optional[str] = None,
             lane: Optional[str] = None,
             args: Optional[dict] = None) -> _Span:
        """A context manager emitting one wall-clock complete event."""
        return _Span(self, name, cat, track, lane, args)

    def complete(self, name: str, t0: float, dur_s: float,
                 cat: str = "", track: Optional[str] = None,
                 lane: Optional[str] = None, ts: Optional[float] = None,
                 args: Optional[dict] = None) -> None:
        """One already-measured span as a complete (``X``) event.

        ``t0`` is the span's start as a ``perf_counter`` reading and
        ``dur_s`` its measured duration — exactly the numbers the
        profiling layer (:class:`repro.backends.jax.profile.
        BucketProfile`) already collects, so instrumentation reuses one
        measurement instead of timing twice.  Simulated-time callers
        pass ``ts=<start seconds>`` instead of ``t0``.
        """
        self._emit("X", name, self._ts_us(ts, t0), cat, track, lane,
                   args, dur=max(0.0, dur_s) * 1e6)

    def instant(self, name: str, cat: str = "",
                track: Optional[str] = None, lane: Optional[str] = None,
                ts: Optional[float] = None,
                args: Optional[dict] = None) -> None:
        """A zero-duration marker (``i``), thread-scoped."""
        self._emit("i", name, self._ts_us(ts, None), cat, track, lane,
                   args, s="t")

    def counter(self, name: str, values: Dict[str, float],
                cat: str = "", track: Optional[str] = None,
                ts: Optional[float] = None) -> None:
        """One sample of a counter track (``C``): ``values`` maps
        series name to value; viewers render multiple series of one
        counter as a stacked area (the power-timeline view)."""
        self._emit("C", name, self._ts_us(ts, None), cat, track, "",
                   {k: float(v) for k, v in values.items()})

    def async_begin(self, name: str, aid: str, cat: str = "",
                    track: Optional[str] = None,
                    ts: Optional[float] = None,
                    args: Optional[dict] = None) -> None:
        """Open an async span (``b``) — spans that start and end on
        different threads, e.g. one service request's submit→resolve
        life.  ``aid`` correlates the matching :meth:`async_end`."""
        self._emit("b", name, self._ts_us(ts, None), cat, track, "",
                   args, id=str(aid))

    def async_end(self, name: str, aid: str, cat: str = "",
                  track: Optional[str] = None, ts: Optional[float] = None,
                  args: Optional[dict] = None) -> None:
        """Close the async span opened under ``aid``."""
        self._emit("e", name, self._ts_us(ts, None), cat, track, "",
                   args, id=str(aid))

    # ------------------------------------------------------------ export
    def events(self) -> List[dict]:
        """A snapshot copy of the collected events."""
        with self._lock:
            return list(self._events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def __bool__(self) -> bool:
        """An installed tracer is truthy even before its first event
        (``__len__`` would otherwise make an empty tracer falsy)."""
        return True

    def to_json(self) -> str:
        """The Chrome JSON array format (one line per event)."""
        evs = self.events()
        lines = ",\n".join(json.dumps(e, sort_keys=True) for e in evs)
        return "[\n" + lines + "\n]\n" if evs else "[]\n"

    def write(self, path: Optional[str] = None) -> str:
        """Serialize to ``path`` (default: the constructor's path)."""
        path = path or self.path
        if not path:
            raise ValueError("no trace output path configured")
        with open(path, "w") as fh:
            fh.write(self.to_json())
        return path


# ---------------------------------------------------------- module API
def get() -> Optional[Tracer]:
    """The installed tracer, or ``None`` when tracing is disabled.
    Hot loops hoist this once instead of paying a check per event."""
    return _TRACER


def enabled() -> bool:
    """True when a tracer is installed."""
    return _TRACER is not None


def install(tracer: Tracer) -> Tracer:
    """Make ``tracer`` the process-wide sink for every instrumented
    layer; returns it for chaining."""
    global _TRACER
    _TRACER = tracer
    return tracer


def uninstall() -> Optional[Tracer]:
    """Disable tracing; returns the tracer that was installed."""
    global _TRACER
    t, _TRACER = _TRACER, None
    return t


def span(name: str, cat: str = "", track: Optional[str] = None,
         lane: Optional[str] = None, args: Optional[dict] = None):
    """Module-level span: a real span when tracing is enabled, the
    shared no-op singleton otherwise (the disabled path allocates
    nothing — it returns the same object every call)."""
    t = _TRACER
    if t is None:
        return _NOOP_SPAN
    return t.span(name, cat=cat, track=track, lane=lane, args=args)


#: ``jax.profiler.TraceAnnotation`` once JAX is loaded; looked up, never
#: imported, so this module stays importable without JAX.
_ANNOTATION = None


def _annotation():
    global _ANNOTATION
    if _ANNOTATION is None:
        _ANNOTATION = getattr(sys.modules.get("jax.profiler"),
                              "TraceAnnotation", None)
    return _ANNOTATION


def region(name: str, track: str, chrome: Optional[str] = None,
           chrome_track: Optional[str] = None, **args):
    """A span at a point of work, on the profiler's clock and the
    tracer's: use it as a context manager.

    While JAX is loaded it enters ``jax.profiler.TraceAnnotation(
    "repro.<track>.<name>", **args)``: the args become stats of the
    event in the profiler's trace (a ``#`` in a string arg is written
    ``%23``, since the annotation's encoding ends at ``#``), while the
    profiler records; with it off the check costs a fraction of a
    microsecond.  Regions mark points of work (a bucket's pack, its
    dispatch), never a wave or a job.  While a :class:`Tracer` is
    installed and ``chrome`` is given, it also emits the Chrome
    complete event ``chrome`` on ``chrome_track`` (default ``track``)
    with category ``track`` and the same args; :meth:`_Region.note`
    adds args known only at the end.  With neither, it is the shared
    no-op.
    """
    ann = _annotation()
    if ann is not None and not ann.is_enabled():
        ann = None
    t = _TRACER if chrome is not None else None
    if ann is None and t is None:
        return _NOOP_SPAN
    if ann is not None:
        ann = ann(f"repro.{track}.{name}",
                  **{k: v.replace("#", "%23") if isinstance(v, str) else v
                     for k, v in args.items()})
    span = None if t is None else t.span(
        chrome, cat=track, track=chrome_track or track, args=args)
    return _Region(ann, span)


def complete(name: str, t0: float, dur_s: float, cat: str = "",
             track: Optional[str] = None, lane: Optional[str] = None,
             ts: Optional[float] = None,
             args: Optional[dict] = None) -> None:
    """Module-level :meth:`Tracer.complete`; no-op when disabled."""
    t = _TRACER
    if t is not None:
        t.complete(name, t0, dur_s, cat=cat, track=track, lane=lane,
                   ts=ts, args=args)


def instant(name: str, cat: str = "", track: Optional[str] = None,
            lane: Optional[str] = None, ts: Optional[float] = None,
            args: Optional[dict] = None) -> None:
    """Module-level :meth:`Tracer.instant`; no-op when disabled."""
    t = _TRACER
    if t is not None:
        t.instant(name, cat=cat, track=track, lane=lane, ts=ts,
                  args=args)


def counter(name: str, values: Dict[str, float], cat: str = "",
            track: Optional[str] = None,
            ts: Optional[float] = None) -> None:
    """Module-level :meth:`Tracer.counter`; no-op when disabled."""
    t = _TRACER
    if t is not None:
        t.counter(name, values, cat=cat, track=track, ts=ts)


def async_begin(name: str, aid: str, cat: str = "",
                track: Optional[str] = None, ts: Optional[float] = None,
                args: Optional[dict] = None) -> None:
    """Module-level :meth:`Tracer.async_begin`; no-op when disabled."""
    t = _TRACER
    if t is not None:
        t.async_begin(name, aid, cat=cat, track=track, ts=ts, args=args)


def async_end(name: str, aid: str, cat: str = "",
              track: Optional[str] = None, ts: Optional[float] = None,
              args: Optional[dict] = None) -> None:
    """Module-level :meth:`Tracer.async_end`; no-op when disabled."""
    t = _TRACER
    if t is not None:
        t.async_end(name, aid, cat=cat, track=track, ts=ts, args=args)


# ------------------------------------------------------ env activation
_env_tracer: Optional[Tracer] = None


def configure_from_env() -> Optional[Tracer]:
    """Install a file-backed tracer when ``REPRO_TRACE=<path>`` is set.

    Idempotent: the first call (run automatically on package import)
    installs the tracer and registers an exit hook that writes the
    file; later calls return the same tracer.  Without the variable it
    does nothing and returns ``None``.
    """
    global _env_tracer
    path = os.environ.get(TRACE_ENV)
    if not path:
        return None
    if _env_tracer is None:
        _env_tracer = Tracer(path=path)
        atexit.register(flush_env_trace)
    return install(_env_tracer)


def flush_env_trace() -> Optional[str]:
    """Write the env-configured tracer's file now (also runs at
    interpreter exit); returns the path or ``None`` when inactive."""
    if _env_tracer is None or not _env_tracer.path:
        return None
    return _env_tracer.write()
