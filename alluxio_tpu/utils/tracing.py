"""Lightweight distributed span tracing + device-profiler bridge.

Re-design of the reference's tracing/profiling surface (SURVEY §5.1:
opentelemetry-style server spans + worker-side profiling hooks). ONE
primitive, ``tracer().span(name, **tags)``, with two independent sinks:

- the **ring**: a process-local deque of recent spans with nesting via
  contextvars, cheap enough to leave compiled in — recording is O(1)
  deque appends gated on one bool (``atpu.trace.enabled``);
- the **device timeline**: in a process that has ALREADY imported
  ``jax`` (this module never imports it, so role processes stay as
  they are) every span also enters a
  ``jax.profiler.TraceAnnotation(name, unix_ns=time.time_ns(), **tags)``.
  Outside a profiler capture that is a no-op C object; inside one
  (``jax.profiler.start_trace``, xprof's capture: MXU occupancy, HBM
  reads, ICI traffic) the span lands on the ``/host:CPU`` plane of the
  ``.xplane.pb``, on the clock of the device's ``XLA Ops`` line, ring
  on or off. The ``unix_ns`` stat anchors that clock to
  ``CLOCK_REALTIME``: :func:`to_trace_clock` lays any ring span of this
  host (client, or worker/master spans out of ``get_trace``) onto it.

Cross-process stitching: every span carries a W3C-traceparent-style
context (``trace_id``, parent ``span_id``, sampled flag). Client stubs
inject ``current_traceparent()`` into RPC metadata; server wrappers
``bind_remote_parent()`` before opening their span, so a read that
crosses client -> worker -> UFS is ONE trace, not three fragments.
Workers drain completed spans to the master on the metrics heartbeat
(``Tracer.drain``); the master stitches them with its own ring in
``TraceStore`` and serves the merged view at ``/api/v1/master/trace``.

Spans surface at ``/api/v1/master/trace`` (master web) and via
``Tracer.snapshot()`` anywhere else. Config: ``atpu.trace.enabled``,
``atpu.trace.sample.rate``, ``atpu.trace.ring.capacity``.
"""

from __future__ import annotations

import contextvars
import os
import random
import re
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, NamedTuple, Optional

_current_span: contextvars.ContextVar = contextvars.ContextVar(
    "atpu_span", default=None)
#: inbound trace context (parsed from RPC metadata) — the parent of the
#: next span opened on this thread of execution when no local span is live
_remote_parent: contextvars.ContextVar = contextvars.ContextVar(
    "atpu_remote_parent", default=None)

_RING_CAP = 4096

#: RPC metadata key carrying the serialized context (gRPC metadata keys
#: must be lowercase)
TRACEPARENT_KEY = "atpu-traceparent"

_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$")

#: The phase-name registry. Every ``Span.phase()`` emit site must use
#: one of these names — atpu-lint's phase analyzer resolves emit sites
#: against this catalog (near-miss typos flagged), and the critical-path
#: analyzer (utils/critical_path.py) attributes span self-time to them.
#: A phase is a *typed slice of wall time inside one span*; it may
#: overlap a child span's interval (e.g. the client's ``wire`` wait
#: covers the server's whole span) — the critical-path analyzer scales
#: phases down to the span's own self-time so nothing double-counts.
PHASES = (
    "queue_wait",   # waiting in an executor/dispatch queue before work ran
    "lock_wait",    # blocked acquiring a block/metadata lock
    "admission",    # QoS admission-control decision on the server
    "serialize",    # msgpack pack/unpack of RPC payloads
    "wire",         # client-observed RPC wait (network + remote service)
    "ufs_fetch",    # reading bytes out of the under-store
    "cache_fill",   # writing fetched bytes into the tiered store
    "tier_read",    # reading bytes out of a local tier
    "device_put",   # host->device transfer (shm staging / jax device_put)
    "drain",        # consumer draining/assembling delivered chunks
    "batch_read",   # server-side scatter/gather assembly of a read_many
    "native_exec",  # GIL-free native execution of a packed read plan
    "table_plan",   # parquet footer fetch/parse + projection range planning
    "table_decode", # pyarrow decode of a planned row group's column chunks
)


class TraceContext(NamedTuple):
    """The propagated slice of a span: W3C trace-context fields."""

    trace_id: str  # 32 lowercase hex chars, not all-zero
    span_id: str   # 16 lowercase hex chars, not all-zero
    sampled: bool


#: id source — a PRNG seeded from the OS, NOT os.urandom per id: ids
#: need uniqueness, not unpredictability, and the urandom syscall costs
#: ~27us/call (measured) — 100x the rest of a span's bookkeeping.
#: Re-seeded on fork so child processes never mint colliding ids.
_ids = random.Random(int.from_bytes(os.urandom(16), "big"))
if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=lambda: _ids.seed(
        int.from_bytes(os.urandom(16), "big")))


def new_trace_id() -> str:
    return f"{_ids.getrandbits(128):032x}"


def new_span_id() -> str:
    return f"{_ids.getrandbits(64):016x}"


def format_traceparent(ctx: TraceContext) -> str:
    """``00-<trace_id>-<span_id>-<flags>`` (W3C traceparent, version 00)."""
    return f"00-{ctx.trace_id}-{ctx.span_id}-{'01' if ctx.sampled else '00'}"


def parse_traceparent(value: Optional[str]) -> Optional[TraceContext]:
    """Parse a traceparent header; None on anything malformed (a bad
    header must degrade to 'new root trace', never to an error)."""
    if not value:
        return None
    m = _TRACEPARENT_RE.match(str(value).strip().lower())
    if m is None:
        return None
    version, trace_id, span_id, flags = m.groups()
    if version == "ff" or trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return TraceContext(trace_id, span_id, bool(int(flags, 16) & 1))


def current_trace_context() -> Optional[TraceContext]:
    """The context a child span (or outbound RPC) should join: the live
    local span first, else an inbound remote parent."""
    span = _current_span.get()
    if span is not None:
        return TraceContext(span.trace_id, span.span_id, span.sampled)
    return _remote_parent.get()


def current_traceparent() -> Optional[str]:
    """Serialized context for RPC injection; None when tracing is off or
    nothing is being traced (so the metadata stays untouched)."""
    if not _TRACER.enabled:
        return None
    ctx = current_trace_context()
    return None if ctx is None else format_traceparent(ctx)


def bind_remote_parent(header: Optional[str]):
    """Bind an inbound traceparent as this execution's parent context.
    Returns a reset token (None when the header is absent/invalid)."""
    ctx = parse_traceparent(header)
    if ctx is None:
        return None
    return _remote_parent.set(ctx)


def reset_remote_parent(token) -> None:
    if token is not None:
        _remote_parent.reset(token)


class Span:
    __slots__ = ("name", "start_ns", "start_ms", "duration_ms", "parent",
                 "span_id", "trace_id", "sampled", "tags", "thread",
                 "error", "phases")

    def __init__(self, name: str, span_id: str, parent: Optional[str],
                 trace_id: str, sampled: bool = True,
                 start_ns: Optional[int] = None) -> None:
        self.name = name
        self.span_id = span_id
        self.parent = parent
        self.trace_id = trace_id
        self.sampled = sampled
        #: CLOCK_REALTIME in ns — what ``to_trace_clock`` maps onto a
        #: profiler capture (``start_ms`` is the same reading, for the
        #: wire format's older consumers)
        self.start_ns = time.time_ns() if start_ns is None else start_ns
        self.start_ms = self.start_ns / 1e6
        self.duration_ms: Optional[float] = None
        self.tags: Dict[str, str] = {}
        self.thread = threading.current_thread().name
        self.error: Optional[str] = None
        #: typed phase events: [name, duration_ms] in emit order; lazily
        #: allocated so spans that never record a phase pay nothing
        self.phases: Optional[list] = None

    def phase(self, name: str, duration_ms: float) -> None:
        """Record a typed phase event (one of ``PHASES``) inside this
        span. O(1) list append; call sites hold the span object (from
        ``with tracer().span(...) as sp`` or ``current_span()``) and
        guard on ``sp is not None``, so the tracing-disabled path never
        reaches here — that guard IS the zero-cost-when-off contract."""
        p = self.phases
        if p is None:
            p = self.phases = []
        p.append((name, duration_ms))

    def to_dict(self) -> dict:
        d = {
            "name": self.name, "span_id": self.span_id,
            "parent": self.parent, "trace_id": self.trace_id,
            "start_ms": round(self.start_ms, 3),
            "start_ns": self.start_ns,
            "duration_ms": None if self.duration_ms is None
            else round(self.duration_ms, 3),
            "thread": self.thread, "tags": self.tags,
            "error": self.error,
        }
        if self.phases:
            d["phases"] = [[n, round(ms, 3)] for n, ms in self.phases]
        return d


class Tracer:
    """Process tracer: bounded ring of completed spans."""

    def __init__(self, capacity: int = _RING_CAP) -> None:
        self.enabled = False
        #: probability a NEW ROOT trace is recorded; children (local and
        #: remote) inherit their parent's decision so traces never tear
        self.sample_rate = 1.0
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()

    def configure(self, *, capacity: Optional[int] = None,
                  sample_rate: Optional[float] = None) -> None:
        if sample_rate is not None:
            self.sample_rate = min(1.0, max(0.0, float(sample_rate)))
        if capacity is not None and capacity != self._ring.maxlen:
            with self._lock:
                self._ring = deque(self._ring, maxlen=max(1, int(capacity)))

    def _sample(self) -> bool:
        rate = self.sample_rate
        return rate >= 1.0 or (rate > 0.0 and random.random() < rate)

    def span(self, name: str, **tags: str):
        """Context manager for one span — the ONLY way to open one. It
        yields the ring :class:`Span`, or None when the ring is off (the
        device-timeline sink is entered either way, see the module
        docstring). ``tags`` are what is known at entry; a tag known
        only at exit goes on the yielded span (``sp.tags[...]``) and so
        reaches the ring alone."""
        return _SpanCtx(self, name, tags)

    def record(self, span: Span) -> None:
        self._ring.append(span)

    def snapshot(self, limit: int = 500,
                 prefix: str = "") -> List[dict]:
        """Most-recent-first dump of completed spans."""
        out = []
        # atomic copy first: iterating the live deque races concurrent
        # record() appends ("deque mutated during iteration")
        for s in reversed(list(self._ring)):
            if prefix and not s.name.startswith(prefix):
                continue
            out.append(s.to_dict())
            if len(out) >= limit:
                break
        return out

    def drain(self, limit: int = 500) -> List[dict]:
        """Pop up to ``limit`` completed spans, oldest first — the
        heartbeat shipping path (spans move to the master's TraceStore
        instead of aging out of this ring)."""
        out: List[dict] = []
        while len(out) < limit:
            try:
                out.append(self._ring.popleft().to_dict())
            except IndexError:
                break
        return out

    def clear(self) -> None:
        self._ring.clear()


#: ``jax.profiler.TraceAnnotation`` once this process has imported jax
#: (None until then: looked up again on the next span, never imported)
_TA = None


def _trace_annotation():
    """The device-timeline sink's class, or None in a process that has
    not imported jax (or is only part-way through importing it)."""
    global _TA
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        _TA = jax.profiler.TraceAnnotation
    except AttributeError:  # another thread is mid-import
        return None
    return _TA


class _SpanCtx:
    __slots__ = ("_tracer", "_name", "_tags", "_span", "_token", "_t0",
                 "_dev")

    def __init__(self, tracer: Tracer, name: str,
                 tags: Dict[str, str]) -> None:
        self._tracer = tracer
        self._name = name
        self._tags = tags
        self._span: Optional[Span] = None

    def __enter__(self) -> Optional[Span]:
        now_ns = time.time_ns()
        ta = _TA or _trace_annotation()
        if ta is None:
            self._dev = None
        else:
            # entered first, left last: the trace twin covers the ring
            # twin; unix_ns is the clock anchor (to_trace_clock)
            self._dev = ta(self._name, unix_ns=now_ns, **self._tags)
            self._dev.__enter__()
        if not self._tracer.enabled:
            return None
        parent = _current_span.get()
        if parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
            sampled = parent.sampled
        else:
            remote = _remote_parent.get()
            if remote is not None:
                trace_id, parent_id = remote.trace_id, remote.span_id
                sampled = remote.sampled
            else:  # new root: this is where the sampling decision lands
                trace_id, parent_id = new_trace_id(), None
                sampled = self._tracer._sample()
        self._span = Span(self._name, new_span_id(), parent_id,
                          trace_id, sampled, start_ns=now_ns)
        if self._tags:
            self._span.tags.update(
                {k: str(v) for k, v in self._tags.items()})
        self._token = _current_span.set(self._span)
        self._t0 = time.perf_counter()
        return self._span

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._span is not None:
            self._span.duration_ms = \
                (time.perf_counter() - self._t0) * 1000.0
            if exc is not None:
                self._span.error = f"{type(exc).__name__}: {exc}"
            _current_span.reset(self._token)
            if self._span.sampled:
                self._tracer.record(self._span)
        if self._dev is not None:
            self._dev.__exit__(exc_type, exc, tb)
        return False


_TRACER = Tracer()


def tracer() -> Tracer:
    return _TRACER


def current_span() -> Optional[Span]:
    """The live local span on this thread of execution, if any — the
    handle phase emit sites use when the span was opened further up the
    stack (e.g. the RPC server wrapper owns the span, the service
    handler records the phases). One contextvar read; None whenever
    tracing is off or the caller is outside any span."""
    return _current_span.get()


def set_tracing_enabled(on: bool) -> None:
    _TRACER.enabled = bool(on)


def apply_trace_conf(conf) -> None:
    """Apply ``atpu.trace.sample.rate`` / ``atpu.trace.ring.capacity``
    to the process tracer (the enabled flag stays with the caller — the
    client only ever turns tracing ON, servers set it absolutely)."""
    from alluxio_tpu.conf import Keys

    _TRACER.configure(
        capacity=conf.get_int(Keys.TRACE_RING_CAPACITY),
        sample_rate=conf.get_float(Keys.TRACE_SAMPLE_RATE))


# -- master-side stitching ---------------------------------------------------
class TraceStore:
    """Spans shipped from remote processes (workers/clients drain their
    rings on the metrics heartbeat), deduplicated by (trace_id, span_id)
    so an in-process cluster — where every role shares one ring — never
    double-serves a span the reporter also shipped."""

    def __init__(self, capacity: int = 8192) -> None:
        self._ring: deque = deque(maxlen=capacity)
        self._seen: "OrderedDict[tuple, bool]" = OrderedDict()
        self._seen_cap = capacity * 2
        self._lock = threading.Lock()

    def ingest(self, source: str, spans: Optional[List[dict]]) -> int:
        n = 0
        with self._lock:
            for s in spans or ():
                if not isinstance(s, dict):
                    continue
                key = (s.get("trace_id"), s.get("span_id"))
                if key in self._seen:
                    continue
                self._seen[key] = True
                while len(self._seen) > self._seen_cap:
                    self._seen.popitem(last=False)
                d = dict(s)
                d.setdefault("source", source)
                self._ring.append(d)
                n += 1
        return n

    def snapshot(self, limit: int = 500, prefix: str = "",
                 trace_id: str = "") -> List[dict]:
        with self._lock:
            items = list(self._ring)
        out = []
        for s in reversed(items):
            if prefix and not str(s.get("name", "")).startswith(prefix):
                continue
            if trace_id and s.get("trace_id") != trace_id:
                continue
            out.append(s)
            if len(out) >= limit:
                break
        return out

    def span_count(self) -> int:
        with self._lock:
            return len(self._ring)


def stitch_spans(store: Optional[TraceStore], *, limit: int = 500,
                 prefix: str = "", trace_id: str = "",
                 local_source: str = "local") -> dict:
    """Merge the process-local ring with remotely-shipped spans into one
    view: a flat most-recent-first span list plus a per-trace summary
    (what ``/api/v1/master/trace`` and ``fsadmin trace`` serve)."""
    spans: List[dict] = []
    seen = set()
    # a trace_id filter scans the whole ring: the wanted trace's spans
    # may sit past the first `limit` recent spans of OTHER traces
    # (the ACTUAL configured capacity, not the default constant)
    scan = max(limit, _TRACER._ring.maxlen or _RING_CAP) \
        if trace_id else limit
    local = _TRACER.snapshot(limit=scan, prefix=prefix)
    for s in local:
        if trace_id and s.get("trace_id") != trace_id:
            continue
        s = dict(s)
        s.setdefault("source", local_source)
        seen.add((s.get("trace_id"), s.get("span_id")))
        spans.append(s)
    if store is not None:
        for s in store.snapshot(limit=limit, prefix=prefix,
                                trace_id=trace_id):
            key = (s.get("trace_id"), s.get("span_id"))
            if key in seen:
                continue
            seen.add(key)
            spans.append(s)
    spans.sort(key=lambda s: s.get("start_ms") or 0.0, reverse=True)
    del spans[limit:]
    return {"spans": spans, "traces": summarize_traces(spans)}


def summarize_traces(spans: List[dict]) -> List[dict]:
    """Per-trace rollup of a most-recent-first span list (span count,
    contributing sources, root name, wall duration). Shared by
    :func:`stitch_spans` and the HA fan-out merge."""
    traces: "OrderedDict[str, dict]" = OrderedDict()
    for s in spans:
        tid = s.get("trace_id")
        if not tid:
            continue
        t = traces.get(tid)
        if t is None:
            t = traces[tid] = {"trace_id": tid, "spans": 0,
                               "sources": [], "root": None,
                               "start_ms": None, "end_ms": None}
        t["spans"] += 1
        src = s.get("source")
        if src and src not in t["sources"]:
            t["sources"].append(src)
        if s.get("parent") is None:
            t["root"] = s.get("name")
        start = s.get("start_ms")
        if start is not None:
            end = start + (s.get("duration_ms") or 0.0)
            t["start_ms"] = start if t["start_ms"] is None \
                else min(t["start_ms"], start)
            t["end_ms"] = end if t["end_ms"] is None \
                else max(t["end_ms"], end)
    for t in traces.values():
        t["duration_ms"] = None if t["start_ms"] is None \
            else round(t["end_ms"] - t["start_ms"], 3)
        t.pop("end_ms", None)
    return list(traces.values())


def to_trace_clock(spans: List[dict], anchor_start_ns: float,
                   anchor_unix_ns: int) -> List[dict]:
    """Lay ring spans onto a profiler capture's clock. Any event the
    device-timeline sink wrote into the capture is an anchor: its
    ``start_ns`` (ns from the capture's start) and its ``unix_ns`` stat
    (``CLOCK_REALTIME`` at the same instant) give the offset between
    the two clocks. Returns copies of ``spans`` (``Span.to_dict`` /
    ``get_trace`` dicts of THIS host: client ring, or a role's spans)
    with ``trace_start_ns`` added; ``start_ns`` is used where a span
    has it, else the 3-decimal ``start_ms``."""
    offset = anchor_start_ns - anchor_unix_ns
    out = []
    for s in spans:
        ns = s.get("start_ns")
        if ns is None:
            ns = s["start_ms"] * 1e6
        out.append({**s, "trace_start_ns": ns + offset})
    return out
