"""The observability catalog: every metric and span name, typed.

Single source of truth for the telemetry namespace. The tables in
``docs/observability.md`` were the original source (this module was
generated from them once, PR 20); from here on the *catalog* is
authoritative — the byzlint ``METRIC-CONTRACT`` rule statically checks
every ``Counter``/``Gauge``/``Histogram`` registration, ``span()``
label, ``jax.named_scope`` label and ``pallas_call(name=...)`` in the
tree against it, and ``tests/test_observability_catalog``
cross-checks the docs tables so prose and code cannot drift.

Adding an instrument is therefore a three-line change: register it at
the call site, add its name here with its type, and row it into
``docs/observability.md``. A name missing from any of the three fails
CI (byzlint exit 1 / docs-parity test).

Pure data, stdlib only — the linter imports this on machines with no
accelerator runtime.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Tuple

#: metric name → instrument type ("counter" | "gauge" | "histogram").
#: One name, one type — enforced statically here and at runtime by
#: :class:`~byzpy_tpu.observability.metrics.MetricsRegistry`.
METRICS: Dict[str, str] = {
    "byzpy_anomaly_flags_total": "counter",
    "byzpy_checkpoint_save_seconds": "histogram",
    "byzpy_client_excluded_total": "counter",
    "byzpy_client_quarantines_total": "counter",
    "byzpy_client_readmits_total": "counter",
    "byzpy_dedup_restaged_total": "counter",
    "byzpy_dedup_staged_total": "counter",
    "byzpy_ingress_batch_size": "histogram",
    "byzpy_jit_compiles_total": "counter",
    "byzpy_overlap_ingest_lag_seconds": "histogram",
    "byzpy_p2p_rounds_total": "counter",
    "byzpy_ps_liveness_probes_total": "counter",
    "byzpy_ps_round_seconds": "histogram",
    "byzpy_ps_rounds_total": "counter",
    "byzpy_quarantined_clients": "gauge",
    "byzpy_recoveries_total": "counter",
    "byzpy_retry_exhausted_total": "counter",
    "byzpy_retry_total": "counter",
    "byzpy_root_finalize_seconds": "histogram",
    "byzpy_root_merge_seconds": "histogram",
    "byzpy_root_partials_inflight": "gauge",
    "byzpy_round_overlap_ratio": "gauge",
    "byzpy_round_repairs_total": "counter",
    "byzpy_serving_bad_frames_total": "counter",
    "byzpy_serving_callback_errors_total": "counter",
    "byzpy_serving_cohort_size": "histogram",
    "byzpy_serving_failed_rounds_total": "counter",
    "byzpy_serving_ingress_bytes_total": "counter",
    "byzpy_serving_malformed_requests_total": "counter",
    "byzpy_serving_outstanding": "gauge",
    "byzpy_serving_quarantines_total": "counter",
    "byzpy_serving_queue_depth": "gauge",
    "byzpy_serving_ragged_recompile_warnings_total": "counter",
    "byzpy_serving_recompile_warnings_total": "counter",
    "byzpy_serving_round_latency_seconds": "histogram",
    "byzpy_serving_rounds_total": "counter",
    "byzpy_serving_submissions_total": "counter",
    "byzpy_serving_submit_frames_total": "counter",
    "byzpy_serving_tenant_dim": "gauge",
    "byzpy_serving_unknown_tenant_total": "counter",
    "byzpy_shard_accepted_total": "counter",
    "byzpy_shard_forged_folds_total": "counter",
    "byzpy_shard_merge_seconds": "histogram",
    "byzpy_shard_partitions_total": "counter",
    "byzpy_shard_quorum_closes_total": "counter",
    "byzpy_shard_rounds_total": "counter",
    "byzpy_shards_live": "gauge",
    "byzpy_slo_breached": "gauge",
    "byzpy_slo_breaches_total": "counter",
    "byzpy_slo_burn_rate": "gauge",
    "byzpy_slo_objective_target": "gauge",
    "byzpy_slo_short_burn_rate": "gauge",
    "byzpy_snapshot_failures_total": "counter",
    "byzpy_speculative_closes_total": "counter",
    "byzpy_step_seconds": "histogram",
    "byzpy_trust_score": "gauge",
    "byzpy_wal_records_total": "counter",
    "byzpy_wire_bytes_total": "counter",
    "byzpy_wire_frames_total": "counter",
    "byzpy_wire_info": "gauge",
}

#: dynamic metric families: a literal name starting with one of these
#: prefixes is catalogued as a family (``byzpy_logged_<key>`` gauges
#: from ``MetricsLogger``)
METRIC_PREFIXES: Tuple[str, ...] = ("byzpy_logged_",)

#: every static span/instant label
SPANS: FrozenSet[str] = frozenset(
    {
        "p2p.aggregate",
        "p2p.round",
        "ps.aggregate",
        "ps.broadcast",
        "ps.fold",
        "ps.fold_finalize",
        "ps.gather",
        "ps.round",
        "serving.admission",
        "serving.broadcast",
        "serving.bucket_pad",
        "serving.client.submit",
        "serving.cohort_close",
        "serving.device_step",
        "serving.fold",
        "serving.fold_merge",
        "serving.gram_assemble",
        "serving.ingress.decode",
        "serving.merge_close",
        "serving.merge_combine",
        "serving.partial_verify",
        "serving.round",
        "serving.round.repair",
        "serving.shard_close",
        "serving.sharded_round",
        "slo.breach",
        "spmd.device_step",
    }
)

#: dynamic span families (``chaos.<kind>`` event-trace mirror instants)
SPAN_PREFIXES: Tuple[str, ...] = ("chaos.",)

#: every ``jax.named_scope`` label inside a jitted step. A scope rides
#: each HLO instruction's ``op_name`` in the compiled program's text (a
#: TPU trace event carries none); ``round.*`` partitions the fused
#: training step, ``serving.*`` names the serving steps' stages. In a
#: round that streams segment by segment ``round.segment_*`` says which
#: pass an op of the model belongs to (``round.fwdbwd`` stays the
#: innermost ``round.*`` of them all), ``model.*`` which part of the model
#: (an op belongs to the LAST ``model.*`` of its path; ``model.mtp`` is an
#: envelope around a whole block) and ``stream.*`` the round's own work
#: inside ``round.fwdbwd`` (``stream.passes``, in the ``(n, d)`` rounds: the
#: loop over the passes of one worker's batch)
SCOPES: FrozenSet[str] = frozenset(
    {
        "model.attention",
        # inside model.attention: the kernels with what their call puts around
        # them (attention_core_device_ms.train; less the kernels' own time,
        # attention_wrap_device_ms.train)
        "model.attention_core",
        # inside model.attention: the products with w_q, w_k, w_v, w_o
        # (attention_proj_device_ms.train)
        "model.attention_proj",
        "model.delta_rule",
        "model.embed",
        "model.hc_maps",
        "model.hc_mix",
        "model.head",
        "model.mla_latent",
        "model.mlp",
        "model.moe_experts",
        "model.moe_route",
        "model.moe_shared",
        "model.mtp",
        "model.mtp_join",
        "model.norm",
        # inside model.attention (latent attention: inside model.mla_latent,
        # whose part by last label it leaves; mla_latent_device_ms.train asks
        # what a path HOLDS and keeps it): the turn by position
        # (rotary_device_ms.train)
        "model.rotary",
        "model.short_conv",
        "model.short_conv_proj",
        "model.ssm_gate",
        "model.ssm_proj",
        "model.ssm_scan",
        "round.aggregate",
        "round.build_matrix",
        "round.fwdbwd",
        "round.param_gather",
        "round.pre_aggregate",
        "round.segment_bwd",
        "round.segment_fwd",
        "round.segment_recompute",
        "round.transpose",
        "round.update",
        "serving.masked_aggregate",
        "serving.opt_update",
        "serving.ragged_aggregate",
        "serving.ragged_dequant",
        "serving.ragged_evidence",
        "serving.ragged_scale",
        "serving.staleness_scale",
        "stream.boundary",
        "stream.passes",
        "stream.rows",
        "stream.shared_rows",
    }
)

#: dynamic scope families (``segment.<key>``: which segment of a streamed
#: round an op belongs to, entered by the round itself for any bundle)
SCOPE_PREFIXES: Tuple[str, ...] = ("segment.",)

#: every ``pl.pallas_call(name=...)``: the enclosing ``_<name>_call``
#: function's name without the underscore and the ``_call``. The name is
#: the custom call's instruction name and a segment of its ``op_name``
#: in the compiled text, so a reader finds the kernel after a refactor.
#: Not every kernel is an aggregate: ``causal_attention_*`` and, where a
#: call has a ``window``, ``window_attention_*`` are the
#: model's (``ops/pallas_attention.py``, under ``model.attention``), and so
#: is ``rows_to_tokens`` (``ops/pallas_rows_to_tokens.py``, under
#: ``model.moe_experts``)
KERNELS: FrozenSet[str] = frozenset(
    {
        "arc_selection_mean_stream",
        "causal_attention_dkv",
        "causal_attention_dq",
        "causal_attention_fwd",
        "clip_selection_mean_stream",
        "dequantize_pallas",
        "dequantize_s4_pallas",
        "gram_pallas",
        "meamed_stream",
        "nnm_selection_mean_stream",
        "nnm_stream",
        "quantize_fp8_pallas",
        "quantize_pallas",
        "quantize_s4_pallas",
        "ragged_segment_sum",
        "ragged_segment_sum_dequant",
        "rows_to_tokens",
        "selection_from_gram",
        "selection_mean_stream",
        "sort_columns",
        "sorted_reduce_stream",
        "sorted_reduce_stream_attacked",
        "weighted_center_step",
        "window_attention_dkv",
        "window_attention_dq",
        "window_attention_fwd",
    }
)

__all__ = [
    "KERNELS",
    "METRICS",
    "METRIC_PREFIXES",
    "SCOPES",
    "SCOPE_PREFIXES",
    "SPANS",
    "SPAN_PREFIXES",
]
