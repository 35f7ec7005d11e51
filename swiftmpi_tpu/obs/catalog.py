"""Declared telemetry series catalog — the ONE list of metric names.

Every instrument registration in the codebase (``reg.counter(...)``,
``reg.gauge(...)``, ``reg.histogram(...)``, the transfer backends'
``_obs_inc`` mirror, the fault bus's ``_obs_count``) must use a name
declared here.  The TELEMETRY-CATALOG lint rule
(:mod:`swiftmpi_tpu.analysis.rules`) enforces the match statically, so
a typo'd series name — or a new series added to one of the four
transfer-backend mirrors but not the others — fails the lint gate
instead of silently forking the dashboard namespace.

Two declaration forms:

* :data:`SERIES` — exact names.  Labels are NOT part of the identity
  here (the registry's ``name{label=v}`` series keys stay free-form);
  the catalog pins the *name* half of the contract that
  docs/ARCHITECTURE.md "Telemetry plane" documents in prose.
* :data:`PREFIXES` — dynamic families built with f-strings whose
  stem is static (``control/<knob-name>`` gauges).  The lint rule
  checks an f-string's leading literal chunk against these.

``transfer/`` series are declared via :data:`TRANSFER_KEYS` (the bare
ledger key, as passed to ``Transfer._obs_inc``) and expanded into
``SERIES`` below, so the ledger key list lives in exactly one place.
"""

from __future__ import annotations

#: Ledger keys mirrored by ``Transfer._obs_inc`` as ``transfer/<key>``.
#: All four backends (local/xla/tpu/hybrid) — including the tpu
#: backend's eager-drain paths, which bypass ``_accum_*`` — must book
#: through keys declared here.
TRANSFER_KEYS = frozenset({
    "wire_bytes", "dispatches",
    "window_sparse", "window_dense",            # legacy 2-way decisions
    "window_fmt",                               # 5-way, fmt= label
    "collective",                               # psum|sparse_ar, kind=
    "hot_psum_bytes_saved",                     # sparse_ar wire delta
    "plan_compiles", "plan_cache_hits",         # TrafficPlan compiler
    "coalesced_rows_in", "coalesced_rows_out",
    "pull_bytes", "pull_rows", "pull_hot_rows",
    "pull_cache_hits", "pull_delta_rows",        # delta-pull cache plane
    "pull_bytes_saved",
    "pull_fmt",                                  # pull decisions, fmt=
    "routed_rows", "overflow_dropped",          # tpu routing ledger
    "hot_rows", "psum_bytes",                   # hybrid hot plane
    "membership_changes",                       # elastic epoch adoptions
})

SERIES = frozenset({
    # host phase spans (obs.span) + bench latency publish default
    "phase_ms", "step_ms",
    # input pipeline (io/pipeline.py)
    "pipeline/produced", "pipeline/consumed", "pipeline/queue_depth",
    # training loops (word2vec/glove via Throughput sampler bridge)
    "train/host_stall_ms_total", "train/words_per_sec",
    # scalar slot lookups the traced step makes for its negatives,
    # mode=per_draw|per_vocab (ops/sampling.alias_slot_lookups)
    "train/sampler_slot_lookups",
    # (center, context) pairs of the host batches fed to the step,
    # kind=valid|grid: the batch's ctx_mask.sum() against its padded
    # (B, 2W) pair grid (models/word2vec._PairCount)
    "train/pairs",
    # checkpoints (io/checkpoint.py)
    "checkpoint/saves", "checkpoint/restores",
    # health probes (utils/health.py)
    "health/probe_ok", "health/probe_fail", "health/probe_ms",
    # fault-injection bus (testing/faults.py)
    "faults/injected", "faults/step_events", "faults/checkpoint_events",
    # serving plane (serve/)
    "serve/queries", "serve/rows_read", "serve/hits", "serve/misses",
    "serve/topk_queries", "serve/latency_ms", "serve/snapshots",
    "serve/snapshot_version", "serve/staleness_steps",
    # snapshot shipping (serve/shipper.py, ISSUE 17): trainer-side
    # publish kind/byte counters (delta_fmt carries a fmt= label) and
    # the replica-side replay gauges ({replica=r<rank>} labeled)
    "serve/delta_publishes", "serve/delta_bytes", "serve/delta_fmt",
    "serve/full_publishes", "serve/full_bytes", "serve/ship_version",
    "serve/replica_version", "serve/replica_lag", "serve/staleness_s",
    # control plane (control/controller.py)
    "control/evaluations", "control/decisions",
    "control/decisions_applied", "control/sketch_observed",
    # fleet observability (obs/collector.py, obs/recorder.py heartbeats)
    "telemetry/heartbeats",
    "fleet/step_ms_skew", "fleet/wire_bytes_imbalance",
    "fleet/members_live", "fleet/members_stalled", "fleet/members_dead",
    "fleet/straggler_rank",
    # numerics health plane (obs/numerics.py, ISSUE 13): in-jit bundle
    # gauges mirrored by NumericsCollector.sampler plus the detector's
    # anomaly severity counter; ef_mass carries a field= label
    "numerics/grad_norm", "numerics/grad_norm_hot",
    "numerics/grad_norm_tail", "numerics/update_ratio", "numerics/loss",
    "numerics/ef_mass", "numerics/nonfinite", "numerics/quant_err",
    "numerics/anomalies",
    # fleet-level numerics mirror (obs/collector.py)
    "fleet/grad_norm_divergence", "fleet/anomalies",
    # compiler & device-cost plane (obs/costs.py, ISSUE 14): per-fn
    # compile/retrace counters and XLA cost/memory-analysis gauges,
    # all labeled fn=<catalog name>
    "compile/compiles", "compile/retraces", "compile/compile_ms",
    "compile/flops", "compile/bytes", "compile/peak_bytes",
    # triggered profiler windows (obs/profiler.py): capture counters
    # and per-phase device/host attribution from the trace parse
    "profile/sessions", "profile/steps",
    "profile/device_ms", "profile/host_ms", "profile/skew_ms",
    # wire-path tracing plane (obs/trace.py, ISSUE 15): flight-recorder
    # volume counters, the last-traced-window gauge smtpu_top's WIN
    # column reads, and the hot-key attribution gauges (key= label)
    "trace/windows", "trace/records", "trace/dumps",
    "trace/last_window_id",
    "trace/hot_key_touches", "trace/hot_key_bytes",
    # elastic membership plane (cluster/membership.py + elastic.py,
    # ISSUE 16): per-rank adopted epoch / workload gauges, the modeled
    # migration-delta traffic, and the fleet-level mirrors
    "elastic/epoch", "elastic/loss", "elastic/rows_owned",
    "elastic/migration_bytes",
    "fleet/epoch", "fleet/reconverge_steps", "fleet/migration_bytes",
    # serve-fleet mirrors (obs/collector.py serve_view, ISSUE 17)
    "fleet/serve_replicas", "fleet/serve_qps", "fleet/serve_lag_max",
    "fleet/serve_version",
}) | frozenset("transfer/" + k for k in TRANSFER_KEYS)

#: Dynamic-name families: an f-string series name passes the catalog
#: check when its leading literal chunk starts with one of these.
PREFIXES = (
    "control/",     # per-knob gauges: control/<knob.name>
)


# -- phase names (ONE list: named scopes, host spans, what they count as) ----

#: ``obs.named_scope`` names entered inside compiled code, each with the
#: phase its device time is booked under — the phase map of a compiled
#: program (``obs.costs.phase_map``), the profiler-window reduction
#: (``obs.profiler``) and the benchmark's ``trace_scope`` reader all read
#: this dict and nothing else.  The tpu/hybrid backends' window dedup is
#: the same phase as the xla backend's sort/segment-sum dedup.
DEVICE_SCOPES = {
    "sample": "sample",            # negative draw + slot lookups + masks
    "pull": "pull",                # Transfer.pull: the row gather
    "math": "math",                # forward, gradient, error norm
    "dedup": "dedup",              # sort / segment sums / mean normalise
    "window_dedup": "dedup",       # transfer/tpu.py, hybrid.py
    "apply": "apply",              # row read-modify-write, AdaGrad
    "wire_exchange": "wire_exchange",
    # the autodiff LM step (models/transformer.py, models/trainer.py): a
    # backward instruction carries its forward scope inside the
    # transform's name (transpose(jvp(experts))) and books there too
    "noise": "noise",              # block diffusion: the step's mask draw
    "embed": "embed",              # the embedding rows' gather
    "conv": "conv",                # gated short convolution operator
    "attention": "attention",      # QKV, QK norm, RoPE, softmax(QK)V, out
    "window_attention": "window_attention",   # ... of a sliding layer
    # a latent layer: down- and up-projections, the two inner norms, RoPE
    # and the rope key's broadcast, softmax(QK)V, out
    "latent_attention": "latent_attention",
    # a sparse layer (learned sparse attention): QKV, QK norm, RoPE, the
    # masked tile walk both ways, out ...
    "sparse_attention": "sparse_attention",
    # ... inside it, the indexer: its projections, LayerNorm and RoPE, every
    # walk of the index scores and the index loss (the KL and the indexer's
    # gradients) ...
    "indexer": "indexer",
    # ... and, innermost, the selection: the exact top-k of every query's
    # row of index scores, packed into bits
    "index_select": "index_select",
    # a Mamba-2 mixer: in / out projections, the causal convolution, the
    # step sizes, the gated grouped norm ...
    "ssm_mixer": "ssm_mixer",
    # ... and, innermost, its chunked recurrence (parallel/ssm.py), forward
    # and transposed
    "ssm_scan": "ssm_scan",
    "route": "route",              # router, top-k, sort, un-sort, weights
    "experts": "experts",          # the grouped products over held experts
    # jax.lax.ragged_dot as the TPU compiler renders it: a custom call
    # whose op_name the compiler's expansion replaces with its own (a
    # kernel's name; only the experts call it)
    "ragged-dot-none": "experts",
    "ragged-dot-metadata": "experts",
    "shared_expert": "shared_expert",   # the experts every token runs
    "dense_ffn": "dense_ffn",      # the dense SwiGLU FFN
    "head": "head",                # final norm, head, cross entropy
    "optimizer": "optimizer",      # clip + AdamW + apply
    # the multi-token-prediction module, everything it adds to a step:
    # merge, gain, its head pass and loss directly under `mtp`, its block's
    # layers under the scopes below (LAYER_SCOPES behind "mtp_")
    "mtp": "mtp",
}

#: the scopes a layer of the LM stack enters.  The multi-token-prediction
#: module traces its block under ``obs.scope_prefix("mtp_")``: the phase of
#: an instruction is its *innermost* known scope, so the module's copies of
#: these carry names of their own, all booked as ``mtp``.  (``ragged_dot``'s
#: custom call keeps no path at all — see ``ragged-dot-none`` above — so the
#: module's grouped products book as ``experts`` with the stack's.)
LAYER_SCOPES = ("conv", "attention", "window_attention", "latent_attention",
                "sparse_attention", "indexer", "index_select",
                "ssm_mixer", "ssm_scan", "route", "experts", "shared_expert",
                "dense_ffn")
DEVICE_SCOPES.update({"mtp_" + s: "mtp" for s in LAYER_SCOPES})

#: device time inside a tracked program but under none of these scopes
#: is reported under this name, never guessed from a shape.
UNSCOPED = "unscoped"

#: ``obs.span`` names (host side: a TraceAnnotation on the profiler's
#: clock + a ``phase_ms{phase=}`` sample).  ``render`` runs on the
#: pipeline's producer thread; the rest on the thread that calls train().
#: Inside one ``Word2Vec.train`` / ``Trainer.run`` call that thread is at
#: any time in ``train_setup``, in an item of the loop (``input_wait``,
#: then the siblings ``step_prep``, ``h2d``, ``dispatch``, ``step_book``),
#: in ``loss_fetch`` (``loss_wait`` is its child: the one block on the
#: queued steps) or in ``train_finish``.  The benchmark's ``trace_host``
#: reader takes its span list from here, so a span declared here is
#: credited there without an edit.
HOST_SPANS = (
    "train_setup", "input_wait", "render", "h2d", "dispatch",
    "loss_fetch", "train_finish", "checkpoint_save", "checkpoint_restore",
    "step_prep", "step_book", "loss_wait",
)

#: ``obs.setup_span`` names (host side, start-up: a TraceAnnotation and a
#: record of the start-up ledger, ``obs.setup_report``, telemetry on or
#: off).  A list of its own: the loop-coverage guard above reads
#: ``HOST_SPANS`` and nothing here is a loop's span.  ``model_build`` is
#: ``Word2Vec.build_from_vocab`` with its children ``table_create``
#: (``Cluster.create_table``: the key index's frame and the table's
#: jitted ``init_all``), ``key_index`` (the vocabulary's slot lookup,
#: ``KeyIndex._create``) and ``sampler_build`` (the alias tables and
#: their placement); ``state_init`` is ``Trainer.init_state``;
#: ``step_build`` the Python that builds a step program; ``first_step``
#: the one call of a tracked program that follows its build
#: (``obs.costs.TrackedFn``); ``kernel_import`` the import of Pallas and its
#: TPU dialect (``utils.xla_env.pallas``), inside whichever span traces
#: the first kernel.
SETUP_SPANS = (
    "model_build", "table_create", "key_index", "sampler_build",
    "state_init", "step_build", "first_step", "kernel_import",
)


def declared(name: str) -> bool:
    """True when ``name`` is a declared series (exact or prefix)."""
    return name in SERIES or any(name.startswith(p) for p in PREFIXES)


def declared_prefix(stem: str) -> bool:
    """True when an f-string whose literal stem is ``stem`` builds
    names inside a declared dynamic family."""
    return any(stem.startswith(p) for p in PREFIXES)
