"""Environment-variable knob registry.

Reference: docs/static_site/src/pages/api/faq/env_var.md (~80 MXNET_*
knobs). On TPU most CUDA/MKLDNN/ps-lite knobs have no analog — XLA owns
kernel tuning and memory — so each documented knob is either WIRED
(changes behavior here), ACCEPTED (read, validated, intentionally a
no-op because XLA/PJRT owns that concern), or absent. ``describe()``
prints the table; ``check()`` warns about set-but-unknown MXNET_ vars
so typos don't silently do nothing.
"""
from __future__ import annotations

import logging
import os

__all__ = ["KNOBS", "describe", "check", "get_int", "get_float",
           "get_bool", "get_str", "markdown_table"]

# name -> (status, consumer, description)
KNOBS = {
    # wired
    "MXNET_ENGINE_TYPE": (
        "wired", "engine.get", "ThreadedEngine (native) | NaiveEngine"),
    "MXNET_CPU_WORKER_NTHREADS": (
        "wired", "engine.Engine", "host worker-pool size"),
    "MXNET_MP_WORKER_NTHREADS": (
        "wired", "gluon DataLoader", "default data-loading workers"),
    "MXNET_KVSTORE_BIGARRAY_BOUND": (
        "wired", "kvstore", "row-shard stored values above this size"),
    "MXNET_CPU_MEM_POOL_DISABLE": (
        "wired", "storage", "disable the pooled host allocator"),
    "MXNET_HOME": ("wired", "model_store/base", "cache directory"),
    "MXNET_LOCK_CHECK": (
        "wired", "utils.locks",
        "ranked-lock witness: 0 (off, raw passthrough) / warn (count "
        "out-of-rank and cycle violations) / error (raise "
        "LockOrderError at the violating acquire); read once at lock "
        "construction"),
    "MXNET_GLUON_REPO": (
        "wired", "model_store", "pretrained-weight repo URL"),
    "MXNET_SEED": (
        "wired", "random", "global PRNG seed applied at import"),
    "MXNET_INT64_TENSOR_SIZE": (
        "wired", "__init__._maybe_enable_int64",
        "enable 64-bit tensors (JAX x64); reference libinfo.h "
        "INT64_TENSOR_SIZE build flag"),
    "MXNET_PROFILER_AUTOSTART": (
        "wired", "profiler", "start profiling at import when 1"),
    "MXNET_TELEMETRY": (
        "wired", "telemetry.tracer",
        "span tracing detail: 0 off (cost is one env read per "
        "site), 1 structural spans (default, also when unset: "
        "SPMDTrainer build and step, compile, parameter init, fused "
        "step, serving lifecycle, pipeline, checkpoint, cache IO), 2 "
        "adds high-frequency spans (per-op dispatch, per-pass graph "
        "opt)"),
    "MXNET_TELEMETRY_BUFFER": (
        "wired", "telemetry.tracer",
        "span ring-buffer capacity (default 8192 events, about 5 MB "
        "when full); on "
        "overflow the oldest events drop and dropped_spans counts "
        "them"),
    "MXNET_ENFORCE_DETERMINISM": (
        "wired", "random/io", "thread-pool decode keeps input order; "
        "all compute is already deterministic under XLA"),
    "MXNET_COORDINATOR": (
        "wired", "tools.launch", "jax.distributed coordinator addr"),
    "MXNET_NUM_PROCESSES": ("wired", "tools.launch", "world size"),
    "MXNET_PROCESS_ID": ("wired", "tools.launch", "process rank"),
    "MXNET_KVSTORE_GC_TYPE": (
        "wired", "kvstore", "gradient compression type via env"),
    "MXNET_KVSTORE_GC_THRESHOLD": (
        "wired", "kvstore", "gradient compression threshold via env"),
    "MXNET_OPTIMIZER_AGGREGATION_SIZE": (
        "wired", "optimizer.SGD", "multi-tensor fused update group size"),
    "MXNET_ENGINE_NUM_LANES": (
        "wired", "engine.Engine", "worker-pool lanes (compute/IO split)"),
    "MXNET_USE_SIGNAL_HANDLER": (
        "wired", "initialize", "crash tracebacks via faulthandler"),
    "MXNET_EAGER_JIT": (
        "wired", "ndarray.registry",
        "compiled eager-dispatch cache; 0 = uncached op-by-op dispatch"),
    "MXNET_EAGER_JIT_CACHE_SIZE": (
        "wired", "ndarray.registry",
        "LRU bound on cached eager-dispatch executables (default 512)"),
    "MXNET_EAGER_JIT_DONATE": (
        "wired", "ndarray.registry",
        "OPT-IN (default 0): donate the out= buffer to the cached "
        "executable when out aliases an input (in-place update "
        "pattern). Donation deletes the old buffer on TPU — only "
        "enable when no detach()/copyto snapshot still references it"),
    "MXNET_DISPATCH_EAGER_PERSIST": (
        "wired", "ndarray.registry",
        "AOT-compile + persist dispatch executables at first-compile "
        "time instead of on the first in-process hit (default 0): a "
        "one-shot construction op never hits twice, so without this "
        "its executable never reaches the disk/remote tier and every "
        "bundle-warm replica re-traces it. Set on bundle-exporting / "
        "remote-publishing replicas; off elsewhere (eager AOT adds "
        "one trace+compile per unique dispatch)"),
    "MXNET_KVSTORE_GAP_TOLERANCE": (
        "wired", "kvstore_ps",
        "dist_async: seconds rank 0 waits on a missing gradient seq "
        "before abandoning it (default 30)"),
    "MXNET_FUSED_STEP": (
        "wired", "gluon.Trainer",
        "compiled fused train-step: allreduce + AMP overflow check + "
        "optimizer update as one donated XLA executable; 0 = eager "
        "per-param fallback"),
    "MXNET_FUSED_STEP_CACHE_SIZE": (
        "wired", "gluon.fused_step",
        "LRU bound on cached fused train-step executables (default 16)"),
    "MXNET_FUSED_STEP_DONATE": (
        "wired", "gluon.fused_step",
        "OPT-IN (default 0): donate PARAMETER buffers to the fused step "
        "executable. Donation deletes the old buffer — only enable when "
        "no tape node / detach() snapshot still references it. "
        "Optimizer state and loss-scale state are always donated"),
    "MXNET_GRAPH_VERIFY": (
        "wired", "analysis",
        "static graph verifier: 0 (default, off) | warn (log "
        "diagnostics) | error (raise GraphVerifyError). Gates "
        "verify-on-bind (executor), verify-on-hybridize (gluon), "
        "donation/aliasing guards (dispatch + fused-step caches) and "
        "SPMD sharding checks; see docs/ANALYSIS.md"),
    "MXNET_GRAPH_OPT": (
        "wired", "analysis.graph_opt",
        "graph-optimization rewrite pipeline (constant folding, CSE, "
        "dead-node elimination, transpose/reshape elision) applied at "
        "the lowering entry points (Executor bind, SymbolBlock "
        "forward/hybridize, serving InferenceSession): 0 (default, "
        "off) | 1 (one pipeline sweep) | 2 (fixpoint). Every optimized "
        "graph is re-verified; new diagnostics reject the rewrite; "
        "see docs/ANALYSIS.md"),
    "MXNET_FUSION": (
        "wired", "kernels + analysis.fusion",
        "fusion-clustering kill switch for the round-17 graph-opt "
        "pass: 1 (default) clusters elementwise chains, "
        "layer_norm+activation, and score/softmax/weighted-sum "
        "attention into single fused kernels-package ops (and arms the "
        "serving fused pad/slice); 0 disables every fusion path while "
        "leaving the rest of MXNET_GRAPH_OPT intact"),
    "MXNET_FUSION_PATTERNS": (
        "wired", "kernels + analysis.fusion",
        "comma list of armed cluster patterns out of elementwise, "
        "norm_act, attention, serving (default: all four); unknown "
        "names are ignored. Part of the compile-cache fingerprint "
        "salt, so toggling never collides cached executables"),
    "MXNET_FUSION_COST_MODEL": (
        "wired", "kernels.cost_model",
        "cluster profitability policy: heuristic (default — fuse when "
        "the saved dispatches beat the estimated bandwidth cost, "
        "Pallas only on TPU at tile-aligned shapes) | always (fuse "
        "every match; bench/debug) | never (match + count but keep "
        "the 1:1 lowering)"),
    "MXNET_QUANTIZE_LOWERING": (
        "wired", "ndarray.ops_quant",
        "how quantized conv/fc/batch_dot execute: auto (default — "
        "native int8 on TPU where the MXU has a fast int8 path, "
        "dequant elsewhere) | native (int8 operands, int32 "
        "accumulation via preferred_element_type) | dequant (operands "
        "converted to fp32 inline, fp32 accumulation rounded back to "
        "the int32 lattice — the fast path on CPU XLA, which has no "
        "native int8 kernels). Part of the quantized-graph "
        "compile-cache fingerprint salt"),
    "MXNET_QUANTIZE_SHADOW": (
        "wired", "serving.repository",
        "fraction (0..1, default 0) of canary requests whose response "
        "is shadow-checked against the incumbent model; used by int8 "
        "canary rollouts to catch accuracy regressions before promote"),
    "MXNET_QUANTIZE_SHADOW_TOL": (
        "wired", "serving.repository",
        "max relative deviation a shadow-checked canary response may "
        "show against the incumbent before the request counts as a "
        "canary failure (default 0.1); failures feed the existing "
        "circuit-breaker rollback"),
    "MXNET_TEST_SEED": (
        "wired", "test_utils",
        "fixed seed for test_utils.set_default_context/seeded test "
        "reruns (tools/flakiness_checker.py sets it per trial)"),
    "MXNET_COMPILE_CACHE": (
        "wired", "utils.compile_cache",
        "persistent compile-artifact cache: on-disk second tier behind "
        "the eager-dispatch and fused-step executable LRUs (serialized "
        "AOT executables + jax persistent-cache fallback), so a warm "
        "process start skips trace+XLA-compile; 0 disables (default 1)"),
    "MXNET_COMPILE_CACHE_DIR": (
        "wired", "utils.compile_cache",
        "directory for the .mxc persistent compile cache (default "
        "<checkout>/.jax_cache/mxc; jax's own cache follows "
        "JAX_COMPILATION_CACHE_DIR, default <checkout>/.jax_cache); "
        "entries are keyed by op/graph "
        "fingerprint + avals + donation + AMP version + "
        "jax/jaxlib/backend/framework versions, corrupt or mismatched "
        "entries are treated as misses and removed"),
    "MXNET_COMPILE_CACHE_MAX_MB": (
        "wired", "utils.compile_cache",
        "size cap on the on-disk compile cache (default 1024); every "
        "32nd write prunes oldest-used .mxc entries down to 80% of the "
        "cap (load refreshes mtime). 0 = unbounded"),
    "MXNET_ARTIFACT_REMOTE": (
        "wired", "artifact.remote",
        "fleet-shared remote artifact-cache URL (file:///shared/dir "
        "or http(s)://host:port speaking GET/PUT /artifacts/<fp>); "
        "replicas consult it behind the local disk tier before "
        "compiling and publish what they compile, so each distinct "
        "fingerprint compiles once per fleet. Unset (default) = no "
        "remote tier"),
    "MXNET_ARTIFACT_REMOTE_PUBLISH": (
        "wired", "artifact.remote",
        "push locally compiled artifacts to the remote store (default "
        "1); 0 makes the replica read-only against the remote tier "
        "(canaries pinned to a blessed artifact set)"),
    "MXNET_ARTIFACT_REMOTE_TIMEOUT_MS": (
        "wired", "artifact.remote",
        "per-request timeout for the http(s) remote artifact backend "
        "(default 2000)"),
    "MXNET_ARTIFACT_REMOTE_RETRIES": (
        "wired", "artifact.remote",
        "attempts per remote artifact round-trip (default 2, via the "
        "resilience RetryPolicy); repeated failures trip a circuit "
        "breaker and the replica degrades to local compiles"),
    "MXNET_ARTIFACT_REMOTE_MAX_MB": (
        "wired", "artifact.remote",
        "byte bound on the remote artifact store (default 512, 0 = "
        "unbounded): file:// publishers prune oldest-used .mxc entries "
        "to 80% of the cap every 32nd publish (concurrent-pruner "
        "tolerant), ArtifactCacheServer evicts least-recently-fetched "
        "blobs on PUT; evictions land in mxnet_artifact_gc_* counters"),
    "MXNET_ARTIFACT_GC_MAX_AGE_S": (
        "wired", "artifact.remote",
        "age bound in seconds on remote artifact-store entries "
        "(default 0 = no age bound): file:// publishers and "
        "ArtifactCacheServer drop entries untouched for longer, "
        "whatever the byte total — only age can reclaim a dead "
        "fingerprint nobody re-publishes (mxnet_artifact_gc_age_"
        "evicted counts them)"),
    "MXNET_ARTIFACT_GC_PROTECT": (
        "wired", "artifact.bundle",
        "os.pathsep-separated deployment-bundle paths whose manifests "
        "pin their fingerprints against remote-store GC (salt-"
        "agnostic; cached by mtime+size). Bundles this process "
        "exported or imported are pinned automatically — skipped "
        "victims land in mxnet_artifact_gc_protected"),
    "MXNET_AUTOTUNE": (
        "wired", "autotune",
        "empirical-autotuning mode: 0 (off — consults return the "
        "hand-written heuristics, the autotune salt contributes "
        "nothing) / consult (default — cost models read persisted "
        "TuningRecords, never measure online) / tune (additionally "
        "allow autotune.tune() sweeps; offline tuning jobs and "
        "benchmarks only, never a serving replica)"),
    "MXNET_AUTOTUNE_DIR": (
        "wired", "autotune.records",
        "directory for persisted TuningRecords (default "
        "$MXNET_HOME/autotune); one <fingerprint>.atr JSON file per "
        "measured decision, written tmp+rename atomic. Records also "
        "ride the MXNET_ARTIFACT_REMOTE store, so one replica's "
        "measurement serves the fleet"),
    "MXNET_AUTOTUNE_BUDGET_MS": (
        "wired", "autotune.tuner",
        "wall-clock budget for one autotune.tune() sweep (default "
        "60000, 0 = unbounded); checked between candidates — the "
        "sweep stops early keeping the best so far"),
    "MXNET_SHAPE_BUCKETS": (
        "wired", "ndarray.registry",
        "automatic batch-axis shape bucketing for eager dispatch: "
        "0 (default, off) | pow2 | mult:N. Whitelisted row-independent "
        "ops are padded up to the bucket boundary before cache lookup "
        "and outputs sliced back, so variable-length streams reuse a "
        "few bucket executables instead of retracing per batch size "
        "(see docs/COMPILE_CACHE.md)"),
    "MXNET_SERVING": (
        "wired", "serving",
        "serving subsystem master switch (default 1): 0 degrades "
        "DynamicBatcher to inline pass-through execution (no queue, no "
        "coalescing) and reports the SERVING runtime feature as off"),
    "MXNET_SERVING_MAX_BATCH": (
        "wired", "serving",
        "largest coalesced batch / largest compiled bucket (default "
        "32); larger direct InferenceSession.predict calls are chunked"),
    "MXNET_SERVING_MAX_LATENCY_MS": (
        "wired", "serving.batcher",
        "micro-batch flush deadline in ms measured from the OLDEST "
        "queued request (default 5): a batch executes when full or "
        "when its first request has waited this long"),
    "MXNET_SERVING_QUEUE_DEPTH": (
        "wired", "serving.batcher",
        "bound on queued requests PER SLO CLASS (default 256); a full "
        "class lane rejects submits with ServerBusy (HTTP 503) — "
        "backpressure, not unbounded buffering, and a best-effort "
        "flood can't evict critical slots"),
    "MXNET_SERVING_TIMEOUT_MS": (
        "wired", "serving.batcher",
        "default per-request deadline in ms (default 2000): a request "
        "still queued past it fails alone with RequestTimeout (HTTP "
        "504) without executing; <= 0 disables"),
    "MXNET_SERVING_WORKERS": (
        "wired", "serving.batcher",
        "batch-formation worker threads (default 1 — right for one "
        "accelerator; more only helps when executions overlap)"),
    "MXNET_SERVING_BUCKETS": (
        "wired", "serving.session",
        "batch-size buckets compiled per model: pow2 (default — powers "
        "of two up to MAX_BATCH) | mult:N | explicit comma list "
        "('1,4,16,32'); MAX_BATCH itself is always included, and an "
        "explicit entry above it is an error (never silently dropped)"),
    "MXNET_SERVING_HOST": (
        "wired", "serving.server",
        "ModelServer bind address (default 127.0.0.1; set 0.0.0.0 to "
        "accept external traffic)"),
    "MXNET_SERVING_PORT": (
        "wired", "serving.server",
        "ModelServer port (default 8080; 0 binds an ephemeral port, "
        "read back via server.port)"),
    "MXNET_SERVING_ADMISSION": (
        "wired", "serving.admission",
        "SLO-aware admission control (default 1): sheds sheddable-"
        "class requests with a fast 503 + Retry-After (ShedLoad) at "
        "submit() when SLO headroom runs out; 0 restores pure "
        "FIFO-with-backpressure semantics"),
    "MXNET_SERVING_SLO_MS": (
        "wired", "serving.admission",
        "latency SLO target in ms for the protected (highest-priority "
        "with traffic) class (default 100): rolling-window p99 against "
        "it forms the latency-headroom signal"),
    "MXNET_SERVING_SHED_HEADROOM": (
        "wired", "serving.admission",
        "headroom floor (default 0.15): best_effort sheds below it, "
        "standard below half of it, critical never (backpressure "
        "only); headroom = min(1 - depth/capacity, 1 - p99/SLO)"),
    "MXNET_SERVING_RETRY_AFTER_MS": (
        "wired", "serving.admission",
        "backoff hint in ms carried by ShedLoad and the HTTP "
        "Retry-After header on admission-shed 503s (default 250)"),
    "MXNET_SERVING_CANARY_FRACTION": (
        "wired", "serving.repository",
        "slice of non-critical traffic routed to a canary version "
        "(default 0.1), deterministic counter-based routing; "
        "critical-class requests never ride a canary; the fleet "
        "router reuses it for replica-level canary shadow pairs"),
    "MXNET_FLEET_VNODES": (
        "wired", "serving.fleet",
        "virtual nodes per replica on the consistent-hash ring "
        "(default 64): more vnodes smooth session placement at the "
        "cost of a larger ring"),
    "MXNET_FLEET_PROBE_MS": (
        "wired", "serving.fleet",
        "fleet router health-gossip interval in ms (default 100): "
        "each round GETs every replica's /healthz, feeds the "
        "per-replica ejection breaker, and refreshes queue-depth "
        "gossip for least-loaded routing and fleet-wide admission"),
    "MXNET_FLEET_TIMEOUT_MS": (
        "wired", "serving.fleet",
        "router->replica HTTP timeout in ms (default 30000) for "
        "forwarded requests, health probes, and drain transfers; a "
        "timeout counts as a transport failure (breaker + retry)"),
    "MXNET_FLEET_DRAIN_TIMEOUT_MS": (
        "wired", "serving.fleet",
        "drain budget in ms (default 10000): bounds the queue-empty "
        "wait during FleetRouter.drain and how long a request for a "
        "mid-drain session parks before its 503"),
    "MXNET_FLEET_RETRIES": (
        "wired", "serving.fleet",
        "cross-replica retries for STATELESS requests after a "
        "transport failure (default 2); stateful requests never "
        "retry across replicas — their state lives on exactly one"),
    "MXNET_SERVING_CANARY_MIN_REQUESTS": (
        "wired", "serving.repository",
        "clean canary completions required before auto-promote "
        "(default 50)"),
    "MXNET_SERVING_CANARY_THRESHOLD": (
        "wired", "serving.repository",
        "canary breaker failure budget (default 3): this many canary "
        "failures — executions or sustained latency regressions — "
        "trip the breaker, which IS the auto-rollback trigger"),
    "MXNET_SERVING_CANARY_LATENCY_X": (
        "wired", "serving.repository",
        "latency-regression multiplier (default 3.0): a canary whose "
        "smoothed latency exceeds this multiple of the incumbent's "
        "counts failures against its breaker"),
    "MXNET_SERVING_STATE_SLOTS": (
        "wired", "serving.state",
        "session-state pool size (default 64): concurrent stateful "
        "streams one SessionStateStore holds device-resident; the "
        "byte budget may shrink the effective count"),
    "MXNET_SERVING_STATE_BUDGET_MB": (
        "wired", "serving.state",
        "session-state pool byte budget in MiB (default 64): caps "
        "slots x per-session state bytes; admission folds the pool's "
        "free fraction into the decision for NEW streams"),
    "MXNET_SERVING_STATE_TTL_S": (
        "wired", "serving.state",
        "idle session time-to-live in seconds (default 600): a "
        "stream untouched this long is evicted before LRU kicks in; "
        "its next step gets a clean retryable SessionEvicted"),
    "MXNET_SERVING_STATE_PAGE_TOKENS": (
        "wired", "serving.state",
        "KV-cache page size in tokens (default 0 = row-slot mode): "
        "> 0 stores pageable state rows (state_row_pageable()) as "
        "fixed-size pages with per-session page tables, so sessions "
        "reserve pages for their live prefix instead of max-length "
        "rows and the byte budget admits several x more streams"),
    "MXNET_SERVING_STATE_KV_INT8": (
        "wired", "serving.state",
        "store fp32 KV pages as symmetric per-page int8 + one fp32 "
        "scale (default 0): halves page bytes again; opt-in and "
        "accuracy-gated by the caller — dequantized attention is "
        "approximate, never bitwise"),
    "MXNET_DEVICE_PREFETCH": (
        "wired", "pipeline.DeviceFeed",
        "device-feed prefetch depth (default 2): batches staged onto "
        "the device AHEAD of the consuming step by a background "
        "thread, so host batch prep + async H2D overlap the compiled "
        "step. 0 = synchronous inline staging — bit-for-bit the "
        "unpipelined loop (see docs/PIPELINE.md)"),
    "MXNET_ASYNC_GRAD_SYNC": (
        "wired", "pipeline.grad_sync / gluon.Trainer",
        "dispatch-as-ready bucketed gradient all-reduce (default 1): "
        "distributed dense grads are bucketed by dtype/size and each "
        "bucket's collective dispatches as soon as backward writes "
        "its grads, overlapping comm with the remaining backward; "
        "0 = one coalesced collective at step() time (the previous "
        "barrier behavior — values are bit-identical either way)"),
    "MXNET_GRAD_BUCKET_KB": (
        "wired", "pipeline.grad_sync",
        "async grad-sync bucket size in KiB (default 512): a dtype "
        "bucket dispatches its all-reduce once pending grads reach "
        "this many bytes; partial buckets flush at step() time"),
    "MXNET_KVSTORE_ASYNC": (
        "wired", "kvstore",
        "OPT-IN (default 0): apply local/single-process kvstore "
        "pushes on the background applier thread so push() returns "
        "immediately and the server-side updater overlaps the next "
        "forward; pull/barrier flush pending updates "
        "(read-your-writes). Multi-process dist types stay "
        "synchronous (collective ordering must match across workers)"),
    "MXNET_DATALOADER_PREFETCH": (
        "wired", "gluon DataLoader",
        "default worker-pool prefetch depth (in-flight batches ahead "
        "of the consumer) for gluon DataLoader when the constructor's "
        "prefetch=None (default 2*num_workers); an explicit "
        "constructor value always wins"),
    "MXNET_RESILIENCE": (
        "wired", "resilience",
        "resilience master switch (default 1): 0 degrades to "
        "fail-fast — retry policies make a single attempt, circuit "
        "breakers never trip, AutoResume propagates the first fault. "
        "Checkpoint writes and the fault-injection harness stay "
        "available either way (see docs/RESILIENCE.md)"),
    "MXNET_CKPT_DIR": (
        "wired", "resilience.checkpoint",
        "default CheckpointManager directory when none is passed "
        "(default $MXNET_HOME/checkpoints)"),
    "MXNET_CKPT_KEEP": (
        "wired", "resilience.checkpoint",
        "keep-last-N checkpoint retention (default 3); older "
        "checkpoints are pruned after each successful write; <= 0 "
        "keeps everything"),
    "MXNET_CKPT_ASYNC": (
        "wired", "resilience.checkpoint",
        "async checkpoint serialization (default 1): snapshots are "
        "captured as immutable device references (+ device copies of "
        "donated buffers) and the D2H transfer + pickle + atomic "
        "write run on a background writer thread off the step loop; "
        "0 writes inline"),
    "MXNET_RESUME_MAX_RESTARTS": (
        "wired", "resilience.AutoResume",
        "restore-and-continue budget per AutoResume.run (default 3); "
        "a fault past the budget raises ResumeExhausted chaining the "
        "last error"),
    "MXNET_RETRY_MAX_ATTEMPTS": (
        "wired", "resilience.RetryPolicy",
        "total attempts (including the first) of the shared "
        "retry/backoff policy (default 4); kvstore_ps sends route "
        "through it"),
    "MXNET_RETRY_BACKOFF_MS": (
        "wired", "resilience.RetryPolicy",
        "base backoff in ms (default 50); doubles per retry with "
        "decorrelated jitter"),
    "MXNET_RETRY_BACKOFF_MAX_MS": (
        "wired", "resilience.RetryPolicy",
        "backoff cap in ms (default 2000)"),
    "MXNET_BREAKER_THRESHOLD": (
        "wired", "resilience.CircuitBreaker",
        "consecutive failures that trip a circuit breaker open "
        "(default 5); serving keeps one breaker per bucket executable"),
    "MXNET_BREAKER_COOLDOWN_MS": (
        "wired", "resilience.CircuitBreaker",
        "open-circuit cooldown in ms before a half-open probe is "
        "admitted (default 30000)"),
    "MXNET_FAULT_PLAN": (
        "wired", "resilience.faults",
        "deterministic fault-injection plan, e.g. "
        "'device_put:at=3;kvstore_push:every=5:times=2' — clauses "
        "fire an exception at registered fault points by call "
        "index/period/seeded probability (docs/RESILIENCE.md lists "
        "the point catalogue and grammar); unset = disarmed "
        "(zero-cost seams)"),
    "MXNET_FAULT_SEED": (
        "wired", "resilience.faults",
        "seed for probabilistic fault clauses (default 0); each "
        "point folds its name in, so streams are deterministic per "
        "(seed, point)"),
    "MXNET_SHARDING": (
        "wired", "sharding",
        "rule-based SPMD sharding subsystem (default 1): plan scopes "
        "drive the fused step, tensor-parallel serving and sharded "
        "checkpoints; 0 makes every plan scope inert (single-device "
        "behavior) without touching caller code; see docs/SHARDING.md"),
    "MXNET_SHARDING_RULES": (
        "wired", "sharding.plan",
        "declarative partition rules for sharding.plan_from_env(), "
        "';'-separated 'regex=axis,axis' entries matched first-wins "
        "against parameter names, e.g. "
        "'.*weight=mp,*;.*embed.*=*,mp' ('*' or empty = replicate "
        "that dim, 'a+b' shards one dim over two mesh axes); unset = "
        "no env-declared plan"),
    "MXNET_SHARDING_UNMATCHED": (
        "wired", "sharding.plan",
        "unmatched-parameter policy for the env-declared plan: "
        "'replicate' (default) or 'error' (a name no rule matches "
        "raises at resolution — audit mode for full-coverage plans)"),
    "MXNET_SHARDING_ZERO1": (
        "wired", "sharding.zero1",
        "opt-in ZeRO-1 cross-replica weight-update sharding (default "
        "0): optimizer-state leaves shard their leading dim over the "
        "mesh's first axis (1/N bytes and 1/N update FLOPs per "
        "device; GSPMD all-gathers the updated weights back to the "
        "plan layout); dims the axis doesn't divide keep the "
        "param-follow layout"),
    # accepted no-ops: the concern is owned by XLA/PJRT on TPU
    "MXNET_EXEC_BULK_EXEC_INFERENCE": (
        "accepted", "-", "XLA fuses whole programs; always bulk"),
    "MXNET_EXEC_BULK_EXEC_TRAIN": (
        "accepted", "-", "XLA fuses whole programs; always bulk"),
    "MXNET_GPU_MEM_POOL_RESERVE": (
        "wired", "storage", "host-pool cap: keep reserve% of RAM unpooled"
        " (HBM itself is PJRT-owned)"),
    "MXNET_GPU_MEM_POOL_TYPE": (
        "wired", "storage", "host-pool strategy: Naive|Round|Unpooled"),
    "MXNET_CUDNN_AUTOTUNE_DEFAULT": (
        "accepted", "-", "XLA autotuning replaces cuDNN autotune"),
    "MXNET_ENABLE_GPU_P2P": ("accepted", "-", "ICI always on"),
    "MXNET_KVSTORE_USETREE": (
        "accepted", "-", "XLA picks the reduction topology"),
    "MXNET_CPU_PRIORITY_NTHREADS": (
        "accepted", "engine", "priority lanes share the one pool"),
    "MXNET_EXEC_NUM_TEMP": ("accepted", "-", "XLA memory planning"),
    "MXNET_GPU_WORKER_NTHREADS": ("accepted", "-", "PJRT streams"),
    "MXNET_GPU_COPY_NTHREADS": (
        "accepted", "engine", "engine IO lane covers host copies"),
    "MXNET_OMP_MAX_THREADS": ("accepted", "-", "XLA:CPU owns threading"),
    "MXNET_MKLDNN_ENABLED": ("accepted", "-", "no MKLDNN; XLA kernels"),
    "MXNET_MKLDNN_CACHE_NUM": ("accepted", "-", "no MKLDNN on TPU"),
    "MXNET_CUDNN_AUTOTUNE_LIMIT": ("accepted", "-", "XLA autotuning"),
    "MXNET_CUDA_ALLOW_TENSOR_CORE": (
        "accepted", "-", "MXU always on; bf16 via AMP/compute_dtype"),
    "MXNET_CUDA_TENSOR_OP_MATH_ALLOW_CONVERSION": (
        "accepted", "-", "bf16 casting is explicit (AMP op lists)"),
    "MXNET_CUDA_LIB_CHECKING": ("accepted", "-", "no CUDA libs"),
    "MXNET_CUDNN_LIB_CHECKING": ("accepted", "-", "no cuDNN"),
    "MXNET_GPU_MEM_POOL_ROUND_LINEAR_CUTOFF": (
        "accepted", "storage", "Round strategy uses a fixed 16KiB cutoff"),
    "MXNET_GPU_MEM_LARGE_ALLOC_ROUND_SIZE": (
        "accepted", "-", "PJRT-owned HBM rounding"),
    "MXNET_ENGINE_OPENMP": ("accepted", "-", "no OpenMP in op bodies"),
    "MXNET_EXEC_ENABLE_INPLACE": (
        "accepted", "-", "XLA buffer aliasing (donated args)"),
    "MXNET_EXEC_MATCH_RANGE": ("accepted", "-", "XLA memory planner"),
    "MXNET_BACKWARD_DO_MIRROR": (
        "wired", "gluon CachedOp / Executor",
        "jax.checkpoint remat: recompute activations in backward"),
    "MXNET_EXEC_INPLACE_GRAD_SUM_CAP": ("accepted", "-", "XLA fusion"),
    "MXNET_KVSTORE_REDUCTION_NTHREADS": (
        "accepted", "-", "reduction is one compiled XLA all-reduce"),
    "MXNET_KVSTORE_SLICE_THRESHOLD": (
        "accepted", "kvstore", "BIGARRAY_BOUND covers sharding"),
    "MXNET_ENABLE_GPU_P2P_CHECK": ("accepted", "-", "ICI topology fixed"),
    "MXNET_CPU_NNPACK_NTHREADS": ("accepted", "-", "no NNPACK"),
    "MXNET_CPU_TEMP_COPY": ("accepted", "-", "XLA-owned"),
    "MXNET_GPU_PARALLEL_RAND_COPY": (
        "accepted", "random", "PRNG is counter-based (jax.random)"),
    "MXNET_RANDOM_RESOURCE_POOL_SIZE": (
        "accepted", "random", "stateless threefry needs no pool"),
    "MXNET_SUBGRAPH_BACKEND": (
        "accepted", "-", "whole-program XLA replaces subgraph backends"),
    "MXNET_SUBGRAPH_VERBOSE": ("accepted", "-", "see profiler traces"),
    "MXNET_USE_FUSION": ("accepted", "-", "XLA fuses unconditionally"),
    "MXNET_FUSION_VERBOSE": ("accepted", "-", "XLA dump flags instead"),
    "MXNET_MODULE_UPDATE_ON_KVSTORE": (
        "accepted", "module", "Module always updates via kvstore updater"),
    "MXNET_UPDATE_ON_KVSTORE": (
        "accepted", "gluon.Trainer", "Trainer decides from kvstore type"),
    "MXNET_IS_WORKER": ("accepted", "tools.launch", "all processes rank"),
    "MXNET_IS_SERVER": (
        "accepted", "tools.launch", "no parameter servers on TPU"),
    "MXNET_IS_SCHEDULER": (
        "accepted", "tools.launch", "jax.distributed coordinator instead"),
    "MXNET_PROFILER_MODE": ("accepted", "profiler", "always all-events"),
    "MXNET_EXEC_VERBOSE_LOGGING": ("accepted", "-", "XLA dump flags"),
    "MXNET_SAFE_ACCUMULATION": (
        "accepted", "-", "fp32 accumulation is always on (MXU native)"),
    "MXNET_MEMORY_OPT": ("accepted", "-", "XLA memory planning"),
}


def get_int(name, default):
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        logging.warning("invalid integer for %s; using %s", name,
                        default)
        return int(default)


def get_float(name, default):
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        logging.warning("invalid float for %s; using %s", name, default)
        return float(default)


def get_bool(name, default=False):
    v = os.environ.get(name)
    if v is None:
        return default
    return v not in ("0", "false", "False", "")


def get_str(name, default=None):
    """String knob read (the one blessed raw-env accessor: graft_lint
    flags direct os.environ reads of MXNET_* names outside this module)."""
    return os.environ.get(name, default)


def describe():
    lines = [f"{name:36s} {status:9s} {desc}"
             for name, (status, _, desc) in sorted(KNOBS.items())]
    return "\n".join(lines)


def check():
    """Warn about set-but-unrecognized MXNET_ vars (typo guard)."""
    unknown = [k for k in os.environ
               if k.startswith("MXNET_") and k not in KNOBS]
    for k in unknown:
        logging.warning("environment variable %s is not recognized by "
                        "mxnet_tpu (see mxnet_tpu.env.describe())", k)
    return unknown


def markdown_table():
    """docs/ENV_VARS.md content, generated from the KNOBS registry so
    the doc can never drift from the code (a tier-1 test asserts the
    committed file matches). Regenerate with::

        python -m mxnet_tpu.env > docs/ENV_VARS.md
    """
    lines = [
        "# `MXNET_*` environment variables",
        "",
        "Generated from the knob registry in `mxnet_tpu/env.py` — do "
        "not edit by hand; regenerate with "
        "`python -m mxnet_tpu.env > docs/ENV_VARS.md`.",
        "",
        "Status **wired** = changes behavior here; **accepted** = read "
        "and validated but intentionally a no-op because XLA/PJRT owns "
        "that concern on TPU (see the module docstring).",
        "",
        "| Variable | Status | Consumer | Description |",
        "| --- | --- | --- | --- |",
    ]
    for name, (status, consumer, desc) in sorted(KNOBS.items()):
        lines.append(f"| `{name}` | {status} | {consumer} | {desc} |")
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    import sys

    sys.stdout.write(markdown_table())
