"""Central span-name registry — the single home of the trace taxonomy.

Every span name the tracer, the metrics histograms, and the reporting
tools agree on lives here and ONLY here (slt-lint rule SLT003): a call
site that spells a span name as a string literal is a lint finding, so
the client taxonomy, the server taxonomy, and ``scripts/trace_report.py``
cannot drift apart silently. ``trace_report.py`` runs standalone
(stdlib-only boxes) and therefore carries a literal fallback copy of the
phase tuples — tests/test_analysis.py pins that copy equal to this
module, which is the drift guard for the one consumer that cannot
import us.

Stdlib-only on purpose: importable by the linter, the report script,
and the watchdog without pulling in numpy or jax.
"""

from __future__ import annotations

# -- client-party spans (obs/trace.py module docstring for semantics) -- #
CLIENT_FWD = "client_fwd"
ENCODE = "encode"
WIRE = "wire"
TRANSPORT = "transport"
CLIENT_BWD = "client_bwd"
OPT_APPLY = "opt_apply"
STEP_TOTAL = "step_total"
# children, never phases: a host<->device copy inside client_fwd /
# client_bwd (and the fused step), with its ``bytes``; a record's
# ``party`` tells the client's ``d2h`` from the server's
H2D = "h2d"
# the parent-less root of one MultiClientSplitRunner round, on the
# driving thread (the clients' threads name it in an attribute)
ROUND = "round"
# the fused step's blocking read of the loss (runtime/fused.py)
LOSS_WAIT = "loss_wait"
# the fused step's read of its counters (below), opened while recording
# only; its attributes are the record: ``layers`` and one list a counter,
# an entry a layer
COUNTERS_READ = "counters_read"

# -- server-party spans ------------------------------------------------ #
QUEUE_WAIT = "queue_wait"
DISPATCH = "dispatch"
D2H = "d2h"

# metrics-histogram-only name (never a trace span — it would
# double-cover ``dispatch`` on a timeline); fed from the ``dispatch``
# span while recording and, under SLT_LOCK_DEBUG=1, by obs/locks.py
# InstrumentedLock
LOCK_HOLD = "lock_hold"

# -- admission control (runtime/admission.py) -------------------------- #
# metrics-only names: counters/gauges the AdmissionController feeds and
# ServerRuntime.metrics() folds in (render_prometheus adds the slt_
# prefix -> slt_admission_*). Deliberately NOT in the phase tuples below:
# admission happens before a request has a trace, and the pinned tuples
# are byte-equal-mirrored by scripts/trace_report.py's stdlib fallback.
ADMISSION_ADMITTED = "admission_admitted"
ADMISSION_REJECTED = "admission_rejected"
ADMISSION_QUEUE_DEPTH = "admission_queue_depth"
# histogram of the advised Retry-After delays handed to rejected callers
ADMISSION_RETRY_AFTER = "admission_retry_after"

# -- decoupled backward / 2BP (runtime/server.py, PR 10) --------------- #
# reply_grad: the client-visible reply window on a decoupled server —
# from dispatch of the reply program (forward + grad-of-activations
# only) to the cut-layer gradient materialized on host. Recorded only
# when --decouple-bwd is on; it is the numerator of the reply-latency
# vs step-latency breakdown trace_report.py prints.
REPLY_GRAD = "reply_grad"
# deferred_apply: one flushed weight-update dispatch (grad-of-weights +
# optimizer apply) running OFF the reply critical path. Like lock_hold
# it must never tile a step's timeline next to ``dispatch`` — a lag=0
# flush happens inside the same lock-held window.
DEFERRED_APPLY = "deferred_apply"

# -- sharded server / pjit (runtime/server.py, PR 11) ------------------ #
# metrics-only counter (the admission_* precedent — never a trace span):
# cumulative bytes moved D2H by the sanctioned sharded-gather helper
# (ServerRuntime._host_gather -> parallel.mesh.host_gather, slt-lint
# SLT013). Incremented only on mesh-sharded servers.
GATHER_BYTES = "gather_bytes"
# chrome-trace metadata event name (ph:"M", not a span): the mesh shape
# + per-program MFU sidecar Tracer.export_chrome(metadata=...) emits and
# trace_report.py's MFU/mesh section reads. NOT in the phase tuples —
# metadata events have no duration to tile a timeline with.
MESH_META = "mesh_meta"

# -- MPMD pipeline / K-stage chain (runtime/stage.py, PR 14) ----------- #
# chrome-trace metadata event name (ph:"M", the MESH_META precedent):
# the per-stage pipeline sidecar the runner's trace_metadata() emits —
# bubble fraction (idle ticks / total ticks, GPipe T = M + S - 1),
# per-hop reply p50, deferred-apply depth — and trace_report.py's
# pipeline section reads. NOT in the phase tuples: metadata events have
# no duration to tile a timeline with.
STAGE_META = "stage_meta"

# -- device-native hops (transport/device.py, PR 16) ------------------- #
# metrics-only counter (the gather_bytes precedent — never a trace
# span): host materializations on the pipeline hop path. The device
# transport's contract is that this stays 0 — the transfer guard is
# inert on the CPU backend (host-platform buffers are zero-copy views),
# so the transports count explicitly and the bench/tests gate on the
# counter. The host-bound transports (http) increment it per hop, which
# is the measured contrast the deploy README cites.
HOP_HOST_COPIES = "hop_host_copies"

# XLA compile events surfaced by obs/dispatch_debug.py under
# SLT_DISPATCH_DEBUG=1 — a recompile storm shows up on the timeline and
# in trace_report.py's compile summary; deliberately NOT in SERVER_PHASES
# (a compile nests inside ``dispatch``, counting both would double-book)
COMPILE = "xla_compile"

# -- flight-recorder events (obs/flight.py, PR 13) --------------------- #
# Causal runtime events — NOT spans (no duration; a flight event is a
# point in a per-process sequence, not a timeline tile) and therefore
# deliberately NOT in the phase tuples below, which trace_report.py's
# stdlib fallback mirrors byte-equal. scripts/postmortem.py carries its
# own literal fallback copy of FLIGHT_EVENTS; tests/test_analysis.py
# pins that copy equal to this tuple (the admission_* precedent).
# slt-lint SLT015 enforces that every ``flight.record(...)`` call site
# names one of these via this registry, never a string literal.
FL_ADMIT = "fl_admit"                    # admission granted (EDF deadline set)
FL_REJECT = "fl_reject"                  # Backpressure raised (quota/queue)
FL_CLAIM_BEGIN = "fl_claim_begin"        # replay claim decided (owner or not)
FL_CLAIM_RESOLVE = "fl_claim_resolve"    # owner published the reply
FL_CLAIM_FAIL = "fl_claim_fail"          # owner failed; claim removed
FL_CLAIM_WAIT = "fl_claim_wait"          # non-owner woke on a resolved claim
FL_REPLAY_HIT = "fl_replay_hit"          # wire-path duplicate served from cache
FL_GROUP_FORM = "fl_group_form"          # request enqueued at the coalescer
FL_GROUP_PICKUP = "fl_group_pickup"      # flusher collected a group
FL_DISPATCH = "fl_dispatch"              # jitted server program dispatched
FL_REPLY = "fl_reply"                    # reply handed back to the caller
FL_DEFER_ENQ = "fl_defer_enqueue"        # deferred weight-apply queued (2BP)
FL_DEFER_APPLY = "fl_defer_apply"        # one deferred apply dispatched
FL_DEFER_FLUSH = "fl_defer_flush"        # deferred queue drained (lag/close)
FL_BREAKER = "fl_breaker"                # circuit breaker state transition
FL_CHAOS = "fl_chaos"                    # fault injected by the chaos wire
FL_CKPT_CAPTURE = "fl_ckpt_capture"      # runtime extras captured (lineage++)
FL_CKPT_COMMIT = "fl_ckpt_commit"        # extras durably committed (rename)
FL_CKPT_LINEAGE = "fl_ckpt_lineage"      # lineage adopted on restore/scan
FL_GATHER = "fl_gather"                  # sanctioned sharded host-gather
FL_SEND = "fl_send"                      # client posted a request
FL_RECV = "fl_recv"                      # party received a request/reply
FL_CLOSE = "fl_close"                    # runtime close entered
FL_WATCHDOG_TRIP = "fl_watchdog_trip"    # lock/dispatch watchdog violation
FL_FATAL = "fl_fatal"                    # SIGTERM / fatal exception dump
# MPMD pipeline hops (PR 14): every event carries ``stage`` (the
# receiving/replying stage index), ``mb`` (microbatch id) and ``dir``
# ("fwd"/"bwd"), so a multi-dump postmortem merge can order one
# microbatch's journey causally across parties and detect per-(stage,
# step) microbatch-order inversions (anomaly ``hop_out_of_order``).
FL_HOP_SEND = "fl_hop_send"              # pipeline hop posted toward a stage
FL_HOP_RECV = "fl_hop_recv"              # pipeline hop delivered/acknowledged
FL_STAGE_REPLY = "fl_stage_reply"        # stage replied (cut grad / acts)
# horizontal replication (PR 15): the router's sticky-routing and
# failover-handoff lifecycle. Every event carries ``replica`` (the
# replica index the event is about) so a merged multi-dump postmortem
# can attribute applies per replica and detect a (client, op, step)
# materialized on two replicas (anomaly ``step_applied_on_two_replicas``).
FL_ROUTE = "fl_route"                    # client -> replica assignment made
FL_REPLICA_DEATH = "fl_replica_death"    # replica declared dead (breaker open)
FL_HANDOFF_BEGIN = "fl_handoff_begin"    # failover handoff started (quiesce)
FL_HANDOFF_COMMIT = "fl_handoff_commit"  # state merged; clients rerouted
# telemetry plane (PR 17): an SLO burn-rate alert transitioned. Carries
# ``tenant``, ``objective`` ("latency"/"availability"), ``state``
# ("firing"/"cleared") and both window burn rates, so a postmortem can
# line the alert up against the admission/dispatch events that caused it.
FL_SLO_ALERT = "fl_slo_alert"            # SLO burn-rate alert fired/cleared
# elastic autoscaling (PR 19): policy-driven scale events. DECISION
# carries ``direction`` ("up"/"down"), ``reason`` and ``executed``;
# UP/DOWN carry ``replica`` (the spawned/retired index) and ``live`` so
# a postmortem can attribute in-flight steps to a departing replica
# (anomaly ``step_lost_to_scale_down``).
FL_SCALE_DECISION = "fl_scale_decision"  # autoscale policy verdict (non-hold)
FL_SCALE_UP = "fl_scale_up"              # replica spawned and adopted
FL_SCALE_DOWN = "fl_scale_down"          # replica retired via policy handoff

# metrics-histogram-only names for the replica router (never trace
# spans — both windows sit inside a client's ``transport`` span and
# would double-cover it on a timeline): the client-visible stall while
# a handoff fence commits, and the router-side quiesce->commit latency.
REPLICA_REROUTE_WAIT = "replica_reroute_wait"
REPLICA_HANDOFF_LATENCY = "replica_handoff_latency"

FLIGHT_EVENTS = (
    FL_ADMIT, FL_REJECT, FL_CLAIM_BEGIN, FL_CLAIM_RESOLVE, FL_CLAIM_FAIL,
    FL_CLAIM_WAIT, FL_REPLAY_HIT, FL_GROUP_FORM, FL_GROUP_PICKUP,
    FL_DISPATCH, FL_REPLY, FL_DEFER_ENQ, FL_DEFER_APPLY, FL_DEFER_FLUSH,
    FL_BREAKER, FL_CHAOS, FL_CKPT_CAPTURE, FL_CKPT_COMMIT,
    FL_CKPT_LINEAGE, FL_GATHER, FL_SEND, FL_RECV, FL_CLOSE,
    FL_WATCHDOG_TRIP, FL_FATAL, FL_HOP_SEND, FL_HOP_RECV,
    FL_STAGE_REPLY, FL_ROUTE, FL_REPLICA_DEATH, FL_HANDOFF_BEGIN,
    FL_HANDOFF_COMMIT, FL_SLO_ALERT, FL_SCALE_DECISION, FL_SCALE_UP,
    FL_SCALE_DOWN)

# -- compressed hop wires (transport/density.py, PR 18) ---------------- #
# metrics-gauge-only name prefix (the admission_* precedent — never a
# trace span): the adaptive density controller's current per-wire
# density, published by the hub as ``wire_density_<wire>`` after each
# decision window (render_prometheus adds the slt_ prefix ->
# slt_wire_density_*). Pairs with the per-runtime
# ``wire_compression_ratio`` gauge the transports feed.
WIRE_DENSITY = "wire_density"

# -- telemetry plane (obs/telemetry.py, PR 17) ------------------------- #
# metrics-gauge-only names (the admission_* precedent — never trace
# spans): the multi-window SLO burn rates the SLOTracker publishes per
# tenant (render_prometheus adds the slt_ prefix -> slt_slo_burn_rate_*).
SLO_BURN_FAST = "slo_burn_rate_fast"
SLO_BURN_SLOW = "slo_burn_rate_slow"

# -- elastic autoscaling (runtime/autoscale.py, PR 19) ----------------- #
# metrics-gauge-only names (the admission_* precedent — never trace
# spans): the router's live replica count and the autoscaler's last
# policy verdict (+1 scale-up, -1 scale-down, 0 hold) — what slt_top's
# fleet table renders per window.
REPLICAS_LIVE = "replicas_live"
AUTOSCALE_DECISION = "autoscale_decision"

# -- device scopes of the afmoe family (models/afmoe.py) --------------- #
# ``jax.named_scope`` names, not host spans (so not in ALL_SPANS): they
# reach a device trace in the operations' names, where the benchmark's
# readers tell window attention from full and find the routed layer's
# parts (the Pallas kernels inside keep the scope as their call's name).
ATTN_WINDOW = "attn_window"
ATTN_FULL = "attn_full"
MOE_ROUTE = "moe_route"      # router, top-k, the pairs' sort and gathers
MOE_EXPERTS = "moe_experts"  # the grouped products over the experts held
MOE_SHARED = "moe_shared"
# -- and of the phi4flash family (models/phi4flash.py), which shares the
# two attention scopes above
SSM_CONV = "ssm_conv"        # the causal depthwise convolution and its silu
SSM_SCAN = "ssm_scan"        # the selective-scan kernel's calls
GMU = "gmu"                  # a gated memory unit, products included
ATTN_CROSS = "attn_cross"    # attention over another layer's keys and values
# -- and of the joyai_llm_flash family (models/joyai_llm_flash.py), which
# shares the three routed-layer scopes above
ATTN_LATENT = "attn_latent"  # the flash calls of a latent-attention layer
MLA_PROJ = "mla_proj"        # its down- and up-projections, norms, rotary
MTP = "mtp"                  # the multi-token-prediction module, whole
# -- and of the lfm2_moe family (models/lfm2_moe.py), which shares
# ``attn_full`` and the routed layer's ``moe_route`` / ``moe_experts``
SHORT_CONV = "short_conv"    # a gated short convolution's gates and taps
# -- and of the nemotron_h family (models/nemotron_h.py), which shares
# ``attn_full``, ``ssm_conv`` and the routed layer's three scopes
SSM_SSD = "ssm_ssd"          # a Mamba-2 recurrence's chunked form (ops/ssd.py)
DEVICE_SCOPES = (ATTN_WINDOW, ATTN_FULL, MOE_ROUTE, MOE_EXPERTS, MOE_SHARED,
                 SSM_CONV, SSM_SCAN, GMU, ATTN_CROSS, ATTN_LATENT, MLA_PROJ,
                 MTP, SHORT_CONV, SSM_SSD)

# -- counters that leave a jitted step (core/stage.with_counters) ------- #
# the flax collection a module sows its step's counters into; mutable
# only where the caller asks for them (the fused step), so every other
# caller traces the program without them. The routed layer
# (models/afmoe.py RoutedExperts) sows three: the pairs each held expert
# got, the row count of the rung it ran, and the static rungs it chose from.
STEP_COUNTERS = "step_counters"
MOE_PAIRS = "pairs"
MOE_ROWS = "rows"
MOE_LADDER = "ladder"
# the looped model's objective (models/ouro.py LoopStage.losses) sows two,
# one entry a pass: the step's mean exit probability and mean cross-entropy
EXIT_MASS = "exit_mass"
EXIT_LOSS = "exit_loss"

# the client-level phases that tile a step — the denominator of the
# compute-vs-wire fraction (encode/wire are sub-phases of transport and
# queue_wait/dispatch belong to the server party; counting either would
# double-book)
CLIENT_PHASES = (CLIENT_FWD, TRANSPORT, CLIENT_BWD, OPT_APPLY)

# server-party span names, for reporting tools
SERVER_PHASES = (QUEUE_WAIT, DISPATCH, D2H)

# the transport decomposition trace_report.py tabulates
TRANSPORT_SUB = (ENCODE, WIRE, QUEUE_WAIT, DISPATCH, D2H)

ALL_SPANS = (CLIENT_FWD, ENCODE, WIRE, TRANSPORT, CLIENT_BWD, OPT_APPLY,
             STEP_TOTAL, QUEUE_WAIT, DISPATCH, D2H, REPLY_GRAD,
             DEFERRED_APPLY, ROUND, H2D, LOSS_WAIT, COUNTERS_READ)
