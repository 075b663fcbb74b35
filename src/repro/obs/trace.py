"""Trace spans — names for the step's phase structure (DESIGN.md §10).

Three span kinds, one naming convention:

* ``phase_span(name, graph=...)`` wraps a block of *traced* optimizer
  code. When ``graph`` is true it enters a ``jax.named_scope``, so the
  ops lowered inside carry the span name as op metadata and a profiler
  capture of the jitted step shows the §8 overlap structure by name;
  otherwise it does nothing. ``graph`` is gated by
  ``EF21MuonConfig.trace_spans`` because op metadata appears in the
  compiled HLO text — the spans-off arm must lower byte-identical to a
  build without this module.

* ``sub_span(name)`` names one level below a phase: the model's
  attention, MLP and vocabulary work inside the gradients phase, the
  TopK selection and EF21 estimate update inside the compress, the
  server's decompress inside the receive. It enters its
  ``jax.named_scope`` only while a ``phase_span(..., graph=True)`` is
  open (a context variable that ``phase_span`` sets), so the models and
  compressors take no flag and the spans-off arm still lowers
  byte-identical.

* ``span(name)`` times a *host-side* (non-jit) phase — plan build,
  layout memoisation, checkpoint I/O, batch making — into the
  process-wide ``SpanRecorder`` (and a ``jax.profiler.TraceAnnotation``,
  so host phases show up in profiler captures too). ``span_summary()``
  renders the recorder as rows for the metrics sink / the train CLI's
  end-of-run table.

Span names are the contract the metadata tests assert against:
``PHASE_SPANS`` for the five algorithm phases of ``core/muon.py``,
``SUB_SPANS`` for the sub-phases, and ``wire_stage_span(direction, k)``
for stage ``k``'s gather/broadcast in ``dist/pipeline.py``'s issue
order.
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from collections import OrderedDict

import jax

# The five algorithm phases of EF21-Muon (core/muon.py, DESIGN.md §5),
# in dataflow order. One span per phase, staged or monolithic.
PHASE_SPANS = (
    "ef21/p1_s2w_update",    # EF21-P model estimate + s2w broadcast
    "ef21/p2_grads",         # per-worker grads at W (vmap, no comm)
    "ef21/p3_ef_compress",   # momentum + EF21 compress R_j = C_D(M_j-G_j)
    "ef21/p4_wire_recv",     # payload gathers issued + server receive
    "ef21/p5_lmo",           # layer-wise LMO (bucketed Newton-Schulz)
)

# One level of sub-phases inside phases 2-4 (``sub_span``). Dotted
# names, so that no JAX primitive's name (``select_n``, ...) can equal
# one as a path segment of an op name.
SUB_SPANS = (
    "lm.attn",        # p2: ln1 + attention + wo + residual, per block
    "lm.mlp",         # p2: ln2 + MLP/MoE FFN + residual, per block
    "lm.vocab",       # p2: token/position lookup, tied head + xent
    "ef.select",      # p3: TopK's threshold passes + topk_select kernel
    "ef.update",      # p3: the sender's own decompress + estimate add
    "ef.decompress",  # p4: the server's payload -> dense delta
)

# True while a graph phase_span is open: sub_span scopes only then.
_GRAPH_PHASE = contextvars.ContextVar("repro_graph_phase", default=False)


def wire_stage_span(direction: str, k: int) -> str:
    """Span name of stage ``k``'s u8 collective: ``direction`` is
    ``"w2s"`` (payload all-gather) or ``"s2w"`` (update broadcast)."""
    if direction not in ("w2s", "s2w"):
        raise ValueError(f"direction must be w2s|s2w, got {direction!r}")
    return f"wire/{direction}/stage{k}"


@contextlib.contextmanager
def phase_span(name: str, graph: bool = False):
    """Span around traced optimizer code: ``jax.named_scope(name)`` when
    ``graph`` (the op-metadata arm the HLO-identity guard keeps off by
    default), nothing otherwise."""
    if not graph:
        yield
        return
    token = _GRAPH_PHASE.set(True)
    try:
        with jax.named_scope(name):
            yield
    finally:
        _GRAPH_PHASE.reset(token)


@contextlib.contextmanager
def sub_span(name: str):
    """``jax.named_scope(name)`` inside an open graph ``phase_span``,
    nothing outside one."""
    if not _GRAPH_PHASE.get():
        yield
        return
    with jax.named_scope(name):
        yield


class SpanRecorder:
    """Thread-safe accumulator of host-side span wall times."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: OrderedDict[str, list] = OrderedDict()

    def record(self, name: str, dur_s: float) -> None:
        with self._lock:
            ent = self._spans.setdefault(name, [0, 0.0, 0.0])
            ent[0] += 1
            ent[1] += dur_s
            ent[2] = max(ent[2], dur_s)

    def summary(self) -> list[dict]:
        """One row per span name (insertion order): count / total / max."""
        with self._lock:
            return [{"name": n, "count": c, "total_s": round(t, 6),
                     "max_s": round(mx, 6)}
                    for n, (c, t, mx) in self._spans.items()]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


# Process-wide recorder: host phases are rare (plan builds, checkpoint
# I/O, per-step host work) and the CLI summary wants them all in one
# place. Tests snapshot/clear around themselves.
RECORDER = SpanRecorder()


@contextlib.contextmanager
def span(name: str, recorder: SpanRecorder | None = None):
    """Wall-time a host-side (non-jit) phase into the recorder, and mark
    it as a TraceAnnotation so profiler captures see it too."""
    rec = RECORDER if recorder is None else recorder
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        try:
            yield
        finally:
            rec.record(name, time.perf_counter() - t0)


def span_summary(recorder: SpanRecorder | None = None) -> list[dict]:
    return (RECORDER if recorder is None else recorder).summary()
