"""Runtime observability (DESIGN.md §10): in-graph metrics, trace
spans, and the unified JSONL metrics sink.

Three pieces, importable leaf-first (nothing here imports repro.core —
the optimizer imports us):

* ``obs.metrics``  — ``MetricSet`` pytree + the norm helpers the step
  collects per layer-plan leaf / NS bucket (gated by
  ``EF21MuonConfig.metrics``; metrics-off lowers identically).
* ``obs.trace``    — ``phase_span``/``sub_span``/``wire_stage_span``
  named scopes for the five optimizer phases, their sub-phases and
  every staged wire collective (op metadata under ``trace_spans``
  only), plus the host-side ``span`` timer for non-jit phases.
* ``obs.sink``     — schema-versioned ``MetricsWriter`` JSONL sink with
  an async flush thread; one validator covers live training logs,
  dry-run rows and the committed BENCH trajectories.
"""
from .metrics import (MetricSet, leaf_names, orth_residual, rel_error,
                      worker_mean_norm)
from .sink import (SCHEMA, MetricsWriter, SchemaError, config_hash,
                   run_manifest, validate_bench_file, validate_jsonl,
                   validate_record, write_bench_artifact)
from .trace import (PHASE_SPANS, RECORDER, SUB_SPANS, SpanRecorder,
                    phase_span, span, span_summary, sub_span,
                    wire_stage_span)

__all__ = [
    "MetricSet", "leaf_names", "orth_residual", "rel_error",
    "worker_mean_norm",
    "SCHEMA", "MetricsWriter", "SchemaError", "config_hash",
    "run_manifest", "validate_bench_file", "validate_jsonl",
    "validate_record", "write_bench_artifact",
    "PHASE_SPANS", "RECORDER", "SUB_SPANS", "SpanRecorder", "phase_span",
    "span", "span_summary", "sub_span", "wire_stage_span",
]
