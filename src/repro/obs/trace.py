"""Span tracer: round → stage → dispatch spans, compile attribution,
device-memory samples, Chrome-trace-event export.

The tracer is wall-clock only — it never touches device values, adds no
jitted calls and costs a few dict appends per stage, so enabling it
cannot perturb ``Validator.trace_counts`` or the seeded telemetry
determinism contract (``tests/test_obs.py`` pins both).

Compile attribution
-------------------
``jax.monitoring`` fires an event-duration callback on every XLA
backend compile (a cache miss — retraces show up here, warm dispatches
don't). JAX has no unregister API, so ONE module-level listener is
installed lazily and consults a per-thread stack of open spans: the
innermost open span at compile time absorbs the seconds into its
``compile_s`` (the bench's "which stage retraced?" question answered
from the trace alone). With no span open the listener is a no-op, so
installation is safe process-wide.

Profiler clock
--------------
While the tracer is enabled, each span also enters a
``jax.profiler.TraceAnnotation`` named ``gauntlet.<cat>.<name>``, so an
active ``jax.profiler`` trace holds the round, stage and dispatch spans
on its host plane beside the device's operations. With no profiler
trace running an annotation costs one check.

Named scopes
------------
The ``SCOPE_*`` names label the work of the peer's DeMo step in the
compiled program (``jax.named_scope`` at the function that does it), so
each HLO instruction's ``op_name`` metadata says which stage it belongs
to, in the peer's step and in the validator's entry points alike.
``BLOCK_SCOPES`` label the parts of a latent-attention and expert
block inside the model's scope. Scopes are trace-time metadata: they
add no operation.

Export is the Chrome trace event format (``ph: "X"`` complete events +
``ph: "C"`` counters + thread-name metadata), loadable in Perfetto
(https://ui.perfetto.dev) or ``about:tracing``. Each span's ``tid`` is
a logical track — the validator uid for round/stage spans — so
concurrent validator pipelines render as parallel rows.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

# ---------------------------------------------------------- named scopes
SCOPE_MODEL = "model"                  # loss_fn / forward; backward is
                                       # what autodiff puts under transpose(
SCOPE_ACCUMULATE = "model.accumulate"  # the micro-batch gradient sum
SCOPE_ENCODE = "demo.encode"           # error-feedback accumulate, DCT
SCOPE_TOPK = "demo.topk"               # magnitude and top-k selection
SCOPE_DECODE = "demo.decode"           # scatter, inverse DCT, residual
SCOPE_APPLY = "demo.apply"             # normalise, sign, update

# The block's parts, nested inside SCOPE_MODEL. A vocabulary of their
# own: the benchmark's classes of the SCOPE_* names (fwd, bwd, the codec
# stages) read the innermost SCOPE_* scope, so these leave them as they
# are, and a reader of the block's split reads these.
BLOCK_MLA = "mla"                      # latent attention (models/mla.py)
BLOCK_MOE_ROUTE = "moe.route"          # router logits, top-k, balance loss
BLOCK_MOE_DISPATCH = "moe.dispatch"    # sort by expert, gather the rows
BLOCK_MOE_EXPERTS = "moe.experts"      # grouped matmuls of held experts
BLOCK_MOE_COMBINE = "moe.combine"      # gate-weighted sum back per token
BLOCK_MOE_SHARED = "moe.shared"        # the shared experts
BLOCK_SCOPES = (BLOCK_MLA, BLOCK_MOE_ROUTE, BLOCK_MOE_DISPATCH,
                BLOCK_MOE_EXPERTS, BLOCK_MOE_COMBINE, BLOCK_MOE_SHARED)

ANNOTATION_PREFIX = "gauntlet."

# ---------------------------------------------------------------- stack
# per-thread stack of open spans; the compile listener reads the top
_TLS = threading.local()


def _stack() -> List["Span"]:
    spans = getattr(_TLS, "spans", None)
    if spans is None:
        spans = _TLS.spans = []
    return spans


_LISTENER_LOCK = threading.Lock()
_LISTENER_INSTALLED = False


def _on_event_duration(name: str, secs: float, **kw) -> None:
    if "backend_compile" not in name:
        return
    spans = _stack()
    if not spans:
        return
    span = spans[-1]
    span.compile_s += secs
    span.compile_events += 1


def _install_listener() -> None:
    global _LISTENER_INSTALLED
    with _LISTENER_LOCK:
        if _LISTENER_INSTALLED:
            return
        try:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration)
        except Exception:
            pass
        _LISTENER_INSTALLED = True


class Span:
    """One open (or closed) trace span. Created via ``SpanTracer``."""

    __slots__ = ("name", "cat", "tid", "ts_us", "dur_us", "compile_s",
                 "compile_events", "args", "_tracer", "_thread",
                 "_annotation")

    def __init__(self, tracer: "SpanTracer", name: str, cat: str,
                 tid: str, ts_us: float, args: Optional[Dict] = None):
        self.name = name
        self.cat = cat
        self.tid = tid
        self.ts_us = ts_us
        self.dur_us: Optional[float] = None
        self.compile_s = 0.0
        self.compile_events = 0
        self.args = dict(args or {})
        self._tracer = tracer
        self._thread = threading.get_ident()
        self._annotation = None


class SpanTracer:
    """Collects spans + counter samples; exports Chrome trace JSON.

    ``enabled=False`` turns every method into a cheap no-op so call
    sites never need their own guard. ``sample_memory_every`` samples
    ``jax`` device ``memory_stats()`` as a counter track once per that
    many closed round spans (0 disables sampling).
    """

    def __init__(self, enabled: bool = True, max_events: int = 200_000,
                 sample_memory_every: int = 1,
                 process_name: str = "gauntlet"):
        self.enabled = enabled
        self.max_events = max_events
        self.sample_memory_every = max(0, int(sample_memory_every))
        self.process_name = process_name
        self.events: List[Dict[str, Any]] = []
        self.dropped = 0
        self.xla_compile_s = 0.0      # total attributed compile seconds
        self.xla_compile_events = 0
        self._epoch = time.perf_counter()
        self._tids: Dict[str, int] = {}
        self._rounds_closed = 0
        self._lock = threading.Lock()
        if enabled:
            _install_listener()

    # ------------------------------------------------------------ time
    def _now_us(self) -> float:
        return (time.perf_counter() - self._epoch) * 1e6

    def _tid(self, name: str) -> int:
        with self._lock:
            tid = self._tids.get(name)
            if tid is None:
                tid = self._tids[name] = len(self._tids) + 1
            return tid

    def _emit(self, event: Dict[str, Any]) -> None:
        with self._lock:
            if len(self.events) >= self.max_events:
                self.dropped += 1
                return
            self.events.append(event)

    # ----------------------------------------------------------- spans
    def begin(self, name: str, cat: str = "span", tid: str = "main",
              **args) -> Optional[Span]:
        """Open a span; pair with :meth:`end`. Spans may close out of
        begin order (concurrent validator pipelines interleave), so the
        attribution stack removes by identity, not LIFO pop."""
        if not self.enabled:
            return None
        import jax
        span = Span(self, name, cat, tid, self._now_us(), args)
        span._annotation = jax.profiler.TraceAnnotation(
            f"{ANNOTATION_PREFIX}{cat}.{name}")
        span._annotation.__enter__()
        _stack().append(span)
        return span

    def end(self, span: Optional[Span]) -> None:
        if span is None or not self.enabled:
            return
        span.dur_us = self._now_us() - span.ts_us
        span._annotation.__exit__(None, None, None)
        spans = _stack() if threading.get_ident() == span._thread else None
        if spans is not None and span in spans:
            spans.remove(span)
        self.xla_compile_s += span.compile_s
        self.xla_compile_events += span.compile_events
        args = dict(span.args)
        if span.compile_s > 0:
            args["xla_compile_ms"] = round(span.compile_s * 1e3, 3)
            args["xla_compiles"] = span.compile_events
        self._emit({"name": span.name, "cat": span.cat, "ph": "X",
                    "ts": round(span.ts_us, 1),
                    "dur": round(span.dur_us, 1),
                    "pid": 1, "tid": self._tid(span.tid),
                    **({"args": args} if args else {})})
        if span.cat == "round":
            self._rounds_closed += 1
            if (self.sample_memory_every
                    and self._rounds_closed % self.sample_memory_every
                    == 0):
                self.sample_memory()

    @contextmanager
    def span(self, name: str, cat: str = "span", tid: str = "main",
             **args):
        sp = self.begin(name, cat, tid, **args)
        try:
            yield sp
        finally:
            self.end(sp)

    def instant(self, name: str, cat: str = "mark", tid: str = "main",
                **args) -> None:
        if not self.enabled:
            return
        self._emit({"name": name, "cat": cat, "ph": "i", "s": "t",
                    "ts": round(self._now_us(), 1), "pid": 1,
                    "tid": self._tid(tid),
                    **({"args": args} if args else {})})

    def counter(self, name: str, values: Dict[str, float],
                tid: str = "counters") -> None:
        """Chrome counter sample (rendered as a stacked area track)."""
        if not self.enabled:
            return
        self._emit({"name": name, "cat": "counter", "ph": "C",
                    "ts": round(self._now_us(), 1), "pid": 1,
                    "tid": self._tid(tid), "args": dict(values)})

    def sample_memory(self) -> Optional[Dict[str, float]]:
        """One ``device.memory_stats()`` sample as a counter event.
        Returns the sampled values (or None when the backend exposes
        none — CPU-only jax builds often return an empty dict)."""
        if not self.enabled:
            return None
        try:
            import jax
            stats = jax.local_devices()[0].memory_stats() or {}
        except Exception:
            stats = {}
        picked = {k: float(stats[k]) for k in
                  ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved")
                  if k in stats}
        if picked:
            self.counter("device.memory", picked)
        return picked or None

    # ---------------------------------------------------------- export
    def to_chrome(self) -> Dict[str, Any]:
        """Chrome trace event JSON dict (Perfetto / about:tracing)."""
        with self._lock:
            tids = sorted(self._tids.items(), key=lambda kv: kv[1])
            events = list(self.events)
        meta: List[Dict[str, Any]] = [
            {"name": "process_name", "ph": "M", "pid": 1,
             "args": {"name": self.process_name}}]
        for name, tid in tids:
            meta.append({"name": "thread_name", "ph": "M", "pid": 1,
                         "tid": tid, "args": {"name": str(name)}})
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped,
                              "xla_compile_s":
                              round(self.xla_compile_s, 6)}}

    def to_chrome_json(self, path: Optional[str] = None) -> str:
        text = json.dumps(self.to_chrome())
        if path:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(path, "w") as f:
                f.write(text + "\n")
        return text
