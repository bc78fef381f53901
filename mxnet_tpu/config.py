"""The MXNET_* environment-knob surface.

Reference counterpart: the ~31 ``MXNET_*`` env vars read through
``dmlc::GetEnv`` across the reference runtime (SURVEY §5.6 tier 2).
Every reference knob is listed here with its TPU-native disposition:

- ``honored``   — changes behavior in this framework (reader cited).
- ``subsumed``  — the concern is owned by XLA/jax (e.g. stream counts,
                  memory pools, kernel tuning); setting it is a no-op by
                  design, not an accident.
- ``accepted``  — parsed and stored for API compatibility; consumers may
                  read it via :func:`get`.

``describe()`` returns the full table (the ``mx.runtime``-style
feature/knob introspection the reference never quite had); ``get``/
``get_int``/``get_bool`` are the typed accessors used by the framework
itself.
"""
from __future__ import annotations

import os

# name -> (default, status, description)
KNOBS = {
    # --- engine (src/engine/) ---
    "MXNET_ENGINE_TYPE": (
        "ThreadedEngine", "honored",
        "host dependency engine implementation (ThreadedEngine|NaiveEngine); "
        "read by engine.create (engine.py)"),
    "MXNET_CPU_WORKER_NTHREADS": (
        "4", "honored",
        "native engine worker thread count (engine.py; src/engine.cc)"),
    "MXNET_CPU_PRIORITY_NTHREADS": (
        "4", "subsumed",
        "priority pool size — XLA async dispatch owns device ordering"),
    "MXNET_GPU_WORKER_NTHREADS": (
        "2", "subsumed", "per-accelerator worker threads — XLA-owned"),
    "MXNET_ENGINE_INFO": (
        "0", "accepted", "verbose engine scheduling logs"),
    # --- executor (src/executor/) ---
    "MXNET_EXEC_BULK_EXEC_TRAIN": (
        "1", "subsumed", "op bulking — jit compiles the whole graph anyway"),
    "MXNET_EXEC_BULK_EXEC_INFERENCE": (
        "1", "subsumed", "op bulking — as above"),
    "MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN": (
        "15", "subsumed", "bulk segment cap — whole-graph jit"),
    "MXNET_EXEC_NUM_TEMP": (
        "1", "subsumed", "temp-space arenas — XLA memory planning"),
    "MXNET_BACKWARD_DO_MIRROR": (
        "0", "honored",
        "recompute-in-backward (sublinear memory): wraps the executor's "
        "fwd+bwd program in jax.checkpoint (executor.py _get_compiled)"),
    "MXNET_EXEC_INPLACE_GRAD_SUM_CAP": (
        "8", "subsumed", "gradient-sum inplace cap — XLA buffer planning"),
    # --- memory (src/storage/) ---
    "MXNET_GPU_MEM_POOL_RESERVE": (
        "5", "subsumed", "device pool watermark — XLA/TPU allocator owns HBM"),
    "MXNET_TPU_HOST_POOL_BYTES": (
        str(1 << 30), "honored",
        "native host storage-pool cap in bytes (storage.py)"),
    # --- kvstore (src/kvstore/) ---
    "MXNET_KVSTORE_REDUCTION_NTHREADS": (
        "4", "subsumed", "CPU reduce threads — reductions compile into XLA"),
    "MXNET_KVSTORE_BIGARRAY_BOUND": (
        str(1000 * 1000), "accepted",
        "big-array server-sharding threshold (serverless design: the DCN "
        "collective is already key-batched, kvstore.py DistKVStore._flush)"),
    "MXNET_KVSTORE_SERIAL_PUSH": (
        "0", "accepted", "serialize push processing"),
    "MXNET_ENABLE_GPU_P2P": (
        "1", "subsumed", "peer-to-peer copies — ICI collectives"),
    # --- cudnn/tuning (disappear into the XLA compiler) ---
    "MXNET_CUDNN_AUTOTUNE_DEFAULT": (
        "0", "subsumed", "conv algo autotuning — XLA picks"),
    "MXNET_USE_OPERATOR_TUNING": (
        "1", "subsumed", "OMP cost-model tuning — XLA fusion"),
    "MXNET_USE_NUM_CORES_OPERATOR_TUNING": (
        "0", "subsumed", "as above"),
    # --- profiler (src/engine/profiler.cc; profiler.py) ---
    "MXNET_PROFILER_MODE": (
        "symbolic", "honored",
        "profiler mode at autostart (symbolic|all) — profiler.py"),
    "MXNET_PROFILER_AUTOSTART": (
        "0", "honored",
        "start the profiler at import; dump on exit — profiler.py"),
    "MXNET_TPU_JAX_TRACE_DIR": (
        "", "honored",
        "also capture a jax/XPlane device trace into this dir when the "
        "profiler runs (profiler.py)"),
    # --- IO ---
    "MXNET_CPU_TEMP_COPY": (
        "4", "subsumed", "IO staging copies — host runtime"),
    # --- distributed roles (dmlc/ps-lite launcher contract) ---
    "DMLC_ROLE": (
        "worker", "honored",
        "worker|server|scheduler — server/scheduler are exit-0 shims in "
        "the serverless design (kvstore_server.py)"),
    "DMLC_PS_ROOT_URI": (
        "", "honored", "coordinator host (dist.py env_spec)"),
    "DMLC_PS_ROOT_PORT": (
        "9091", "honored", "coordinator port (dist.py env_spec)"),
    "DMLC_NUM_WORKER": (
        "1", "honored", "world size (dist.py env_spec)"),
    "DMLC_WORKER_ID": (
        "0", "honored", "worker rank (dist.py env_spec)"),
    # --- input pipeline / fit hot loop (ISSUE 5) ---
    "MXNET_TPU_FEED_DEPTH": (
        "2", "honored",
        "DeviceQueueIter bounded pipeline depth: batches staged on the "
        "mesh ahead of the consumer (parallel/feed.py)"),
    "MXNET_TPU_MAX_INFLIGHT": (
        "2", "honored",
        "fused fit loop dispatch-ahead bound: compiled steps in flight "
        "before the host throttles (module/spmd_group.py)"),
    "MXNET_TPU_DEVICE_METRICS": (
        "1", "honored",
        "fold per-batch metric stats computed inside the compiled step "
        "into device accumulators; host device_get only at Speedometer/"
        "epoch boundaries (module/spmd_group.py, metric.py)"),
    # --- weight-update sharding / ZeRO (ISSUE 7) ---
    "MXNET_TPU_ZERO": (
        "0", "honored",
        "shard the weight update (reduce-scatter grads, update a 1/N "
        "optimizer-state shard, all-gather weights) over the data axes "
        "of the fused SPMD step — arXiv:2004.13336 (parallel/spmd.py, "
        "module/spmd_group.py); 0|1, anything else raises"),
    "MXNET_TPU_ZERO_WIRE": (
        "raw", "honored",
        "gradient-shard wire treatment inside the ZeRO step: 'raw' or "
        "'2bit' (the PR 4 error-feedback two-bit quantizer applied to "
        "the reduce-scattered shard; residual is 1/N-sharded too) "
        "(parallel/spmd.py)"),
    "MXNET_TPU_ZERO_WIRE_THRESHOLD": (
        "0.5", "honored",
        "ternary threshold for MXNET_TPU_ZERO_WIRE=2bit; finite float "
        "> 0 (parallel/spmd.py)"),
    "MXNET_TPU_ZERO_MIN_SIZE": (
        "1024", "honored",
        "parameters with fewer elements keep replicated optimizer "
        "state (sharding tiny biases costs more collective latency "
        "than the bytes saved); shared by the fused tier and the "
        "dist_async value-sharded server tier (parallel/spmd.py, "
        "kvstore_server.py)"),
    "MXNET_TPU_ZERO_SERVER": (
        "0", "honored",
        "dist_async mirror of weight-update sharding: slice each "
        "large dense key's value AND optimizer state across ALL "
        "servers (push scatters slices, pull gathers) so per-server "
        "memory scales 1/num_servers; must be set job-wide "
        "(kvstore_server.py); 0|1, anything else raises"),
    # --- self-healing training (ISSUE 9) ---
    "MXNET_TPU_SENTINEL": (
        "off", "honored",
        "in-graph anomaly sentinel for the fused step: per-step health "
        "word (finite loss, global grad norm, updated params) computed "
        "INSIDE the compiled program with device-resident counters — "
        "no per-batch host sync. off|record|skip|halt: 'record' only "
        "counts, 'skip' additionally turns an unhealthy step into a "
        "no-op (pre-update params/opt-state selected via jnp.where — "
        "bit-identical params), 'halt' checks the health word on host "
        "EVERY step (a per-batch sync, counted in host_syncs) and "
        "raises on the first unhealthy one (parallel/spmd.py)"),
    "MXNET_TPU_GUARD": (
        "1", "honored",
        "arm the Module.fit self-healing guardrail when a coordinated "
        "checkpoint directory (MXNET_CHECKPOINT_DIR) is configured: "
        "consecutive-unhealthy / loss-spike detection triggers a "
        "coordinated rollback to CheckpointManager.latest() with LR "
        "backoff (health.py); 0|1, anything else raises"),
    "MXNET_TPU_GUARD_CONSEC": (
        "3", "honored",
        "consecutive unhealthy steps (fused: the sentinel's device "
        "consec counter; host tier: consecutive non-finite-output "
        "batches) that trigger a rollback (health.py)"),
    "MXNET_TPU_GUARD_SPIKE": (
        "10.0", "honored",
        "loss-spike rollback trigger: a checked loss above this ratio "
        "of its running EMA rolls back; 0 disables spike detection "
        "(health.py)"),
    "MXNET_TPU_GUARD_BACKOFF": (
        "0.5", "honored",
        "learning-rate multiplier applied on every rollback (in (0, "
        "1]); applied server-side on dist_async via the rollback RPC "
        "and via a fused-step rebuild on kvstore='tpu' (health.py)"),
    "MXNET_TPU_GUARD_BUDGET": (
        "2", "honored",
        "bounded rollback budget: after this many rollbacks the next "
        "trigger fails the job loudly (elastic supervision resumes it "
        "from the last checkpoint) instead of looping (health.py)"),
    "MXNET_TPU_GUARD_INTERVAL": (
        "10", "honored",
        "fused-tier guard check cadence in batches: the sentinel "
        "counters are drained (one blocking device read) every N "
        "batches, amortized like the Speedometer (health.py)"),
    "MXNET_PREEMPT_GRACE": (
        "15", "honored",
        "preemption grace window in seconds: on SIGTERM/SIGINT a "
        "launch.py-spawned worker drains in-flight steps and writes a "
        "resumable checkpoint, then exits with the distinguished "
        "EXIT_PREEMPTED status; a hard-exit timer guarantees the "
        "process is gone within the window either way (health.py)"),
    # --- elastic recovery / fault injection (ISSUE 3, registered here
    # per the ISSUE 9 knob-drift audit) ---
    "MXNET_CHECKPOINT_DIR": (
        "", "honored",
        "coordinated checkpoint directory (CheckpointManager.from_env; "
        "exported by tools/launch.py to every role)"),
    "MXNET_CHECKPOINT_PERIOD": (
        "1", "honored", "checkpoint every N epochs (checkpoint.py)"),
    "MXNET_CHECKPOINT_RETAIN": (
        "2", "honored", "newest complete checkpoints kept (checkpoint.py)"),
    "MXNET_MAX_RESTARTS": (
        "0", "honored",
        "elastic respawn budget per node; > 0 switches the tracker and "
        "server barriers into elastic mode (tracker.py, launch.py)"),
    "MXNET_FAULT_SPEC": (
        "", "honored",
        "deterministic fault injection rules (chaos.py grammar: "
        "crash/nan/preempt @step, rpc drop, heartbeat stall)"),
    # --- kvstore data plane (ISSUE 4, registered per the drift audit) ---
    "MXNET_KVSTORE_PIPELINE": (
        "1", "honored",
        "async per-shard sender pipeline for the server tier; 0 falls "
        "back to the synchronous client (kvstore_server.py)"),
    "MXNET_KVSTORE_RPC_RETRIES": (
        "2", "honored",
        "bounded kvstore RPC retries with reconnect + server "
        "rediscovery (kvstore_server.py)"),
    "MXNET_KVSTORE_RECONNECT_DEADLINE": (
        "5", "honored", "seconds per reconnect attempt (kvstore_server.py)"),
    "MXNET_KVSTORE_REDISCOVER_TIMEOUT": (
        "30", "honored",
        "seconds to wait for a respawned server's new URI via the "
        "tracker (kvstore_server.py)"),
    "MXNET_KVSTORE_COALESCE_KEYS": (
        "16", "honored", "max keys per coalesced push_multi frame"),
    "MXNET_KVSTORE_COALESCE_BYTES": (
        str(1 << 20), "honored", "max bytes per coalesced push_multi frame"),
    "MXNET_KVSTORE_BARRIER_TIMEOUT": (
        "120", "honored",
        "server barrier timeout in seconds — raises instead of "
        "spinning (kvstore_server.py)"),
    # --- tracker / process topology (ISSUE 2, registered per the
    # drift audit; the per-role DMLC-style identity vars launch.py
    # sets are allowlisted in tests/test_knob_registry.py instead) ---
    "MXNET_TRACKER_HEARTBEAT_INTERVAL": (
        "2.0", "honored", "client heartbeat period in seconds (tracker.py)"),
    "MXNET_TRACKER_HEARTBEAT_TIMEOUT": (
        "30.0", "honored",
        "scheduler-side beat-loss dead-node threshold (tracker.py)"),
    "MXNET_TRACKER_BARRIER_TIMEOUT": (
        "120", "honored", "tracker barrier timeout in seconds (tracker.py)"),
    "MXNET_PS_SERVER_URI": (
        "", "honored",
        "manual server URI list for deployments without the tracker "
        "rendezvous (kvstore_server.py)"),
    "MXNET_PS_BIND_HOST": (
        "", "honored", "server bind host override (kvstore_server.py)"),
    "MXNET_PS_BIND_PORT": (
        "0", "honored", "server bind port override (kvstore_server.py)"),
    "MXNET_PS_ADVERTISE_HOST": (
        "", "honored",
        "address a multi-host server publishes to the tracker "
        "(kvstore_server.py)"),
    # --- Pallas schedule autotuner (ISSUE 10) ---
    "MXNET_TPU_TUNE": (
        "1", "honored",
        "consult the on-disk schedule table for searched Pallas kernel "
        "schedules at trace time (kernels consult tune.schedule_for "
        "with the hand defaults as fallback — an empty table is "
        "bit-identical to the pre-autotuner behavior); 0 pins the hand "
        "defaults (tune/table.py)"),
    "MXNET_TPU_TUNE_TABLE": (
        "", "honored",
        "schedule-table path override (default ~/.cache/mxnet_tpu/"
        "schedule_table.json); written atomically by "
        "tools/tune_kernels.py, keyed (kernel, shape, dtype, backend) "
        "(tune/table.py)"),
    # --- learned cost model / ranked sweeps / background tuning
    # (ISSUE 15) ---
    "MXNET_TUNE_RANKER": (
        "1", "honored",
        "rank sweep candidates with the learned cost model and time "
        "only the top MXNET_TUNE_TOPK (hand default always timed as "
        "baseline); the ranker abstains into the exhaustive sweep when "
        "the model is missing, under-trained, or below the validation "
        "rank-correlation floor — 0 pins the PR 10 exhaustive sweep "
        "(tune/search.py)"),
    "MXNET_TUNE_TOPK": (
        "3", "honored",
        "how many model-ranked candidates a ranked sweep times, on top "
        "of the always-timed hand default (tune/search.py)"),
    "MXNET_TUNE_MODEL": (
        "", "honored",
        "cost-model path override (default: next to the schedule "
        "table, <table>.model.json); versioned JSON written atomically "
        "by model refits — corrupt files log, behave as absent, and "
        "are rewritten whole by the next fit (tune/model.py)"),
    "MXNET_TUNE_BACKGROUND": (
        "0", "honored",
        "arm tune.BackgroundTuner in Module.fit: bounded tuning slots "
        "at epoch/checkpoint drain boundaries for shapes the job "
        "traced (schedule-table misses), never inside the steady-state "
        "step loop (tune/background.py)"),
    "MXNET_TUNE_BG_BUDGET": (
        "2", "honored",
        "max timed programs per background-tuning slot, hand default "
        "included (tune/background.py)"),
    # --- misc registered per the drift audit ---
    "MXNET_TPU_FUSED_ROW_TILE": (
        "", "honored",
        "fused Pallas kernel row-tile override; strict-parsed (a "
        "malformed value raises with the knob name) and cached per "
        "value (kernels/fused_block.py)"),
    "MXNET_GLUON_REPO": (
        "", "honored",
        "gluon model-zoo repo URL or local directory "
        "(gluon/model_zoo/model_store.py)"),
    "MXNET_INFER_DEBUG": (
        "0", "honored",
        "full tracebacks from shape/type inference failures "
        "(executor.py)"),
    # --- serving tier (ISSUE 6) ---
    "MXNET_SERVE_BATCH_LADDER": (
        "1,4,16,64", "honored",
        "comma-separated batch-size buckets the AOT predictor binds; "
        "requests pad up to the nearest bucket (serving/predictor.py; "
        "malformed or non-increasing ladders raise)"),
    "MXNET_SERVE_QUEUE_DEPTH": (
        "256", "honored",
        "per-model bounded request queue; a full queue backpressures "
        "submit() (serving/broker.py)"),
    "MXNET_SERVE_MAX_EXECUTABLES": (
        "32", "honored",
        "LRU capacity of compiled (model, bucket, dtype) executables "
        "shared by all resident models (serving/predictor.py)"),
    "MXNET_SERVE_SUBMIT_TIMEOUT": (
        "60", "honored",
        "seconds submit() may block on backpressure before raising "
        "(serving/broker.py)"),
    # --- graph IR passes + quantized serving (ISSUE 13) ---
    "MXNET_IR_PASSES": (
        "fusion", "honored",
        "default pass pipeline for ir.apply_passes(passes=None): a "
        "comma list of registered pass names (fusion|residual|"
        "quantize); unknown names raise naming this knob "
        "(ir/passes.py)"),
    "MXNET_IR_FUSE": (
        "1", "honored",
        "kill switch for rule-based fusion in the model builders: "
        "build_resnet(fused=True) applies the IR fusion pass when 1, "
        "returns the unfused graph when 0 (models/resnet.py); 0|1, "
        "anything else raises"),
    "MXNET_SERVE_QUANT": (
        "none", "honored",
        "default serving quantization mode when AOTPredictor "
        "quant=None: 'none' or 'int8' (int8 needs calib_data= — "
        "asking without it raises CalibrationError) "
        "(serving/predictor.py, ir/quantize.py)"),
    "MXNET_QUANT_CALIB_BATCHES": (
        "8", "honored",
        "max calibration batches the int8 quantization pass consumes "
        "from the provided calibration data; integer >= 1 "
        "(ir/quantize.py)"),
    "MXNET_TPU_REMAT": (
        "0", "honored",
        "default rematerialization mode when TrainStep(remat=None): "
        "0|off = none, 1 = full recompute, conv = save MXU-primitive "
        "outputs; anything else raises (parallel/spmd.py)"),
    # --- serving fleet (ISSUE 11) ---
    "MXNET_FLEET_RETRIES": (
        "2", "honored",
        "router retry budget per request BEYOND the first attempt: "
        "never-sent failures and admission rejections (draining/"
        "closed/overloaded) retry on a DIFFERENT replica, in-flight "
        "losses retry only for idempotent requests; integer >= 0 "
        "(serving/fleet.py)"),
    "MXNET_FLEET_TIMEOUT": (
        "30", "honored",
        "per-request end-to-end deadline budget in seconds across ALL "
        "router attempts (also forwarded to the replica as the "
        "deadline-at-dequeue shed bound); finite float > 0 "
        "(serving/fleet.py)"),
    "MXNET_FLEET_BACKOFF": (
        "0.05", "honored",
        "base exponential backoff in seconds between router retry "
        "attempts (doubles per attempt, capped at 1 s); finite float "
        ">= 0 (serving/fleet.py)"),
    "MXNET_FLEET_VIEW_INTERVAL": (
        "2.0", "honored",
        "tracker-view refresh period in seconds: the router re-reads "
        "the replica membership/load gauges, and each replica "
        "re-publishes its load at the same cadence; finite float > 0 "
        "(serving/fleet.py)"),
    "MXNET_FLEET_CONNECT_DEADLINE": (
        "5.0", "honored",
        "seconds the router spends connecting to one replica before "
        "counting the attempt as never-sent and failing over; finite "
        "float > 0 (serving/fleet.py)"),
    "MXNET_SERVE_DRAIN_TIMEOUT": (
        "30", "honored",
        "seconds a draining replica waits for queued + in-flight "
        "requests to finish before the drain RPC errors (the rolling "
        "fleet_swap bound); finite float > 0 (serving/fleet.py)"),
    # --- generative serving (ISSUE 12) ---
    "MXNET_GENERATE_MAX_STEPS": (
        "256", "honored",
        "decode-step cap per generate request (also the default "
        "max_new_tokens): a request that never emits EOS — wedged "
        "client, chaos generate:stall — finishes with reason 'length' "
        "at this many generated tokens and its slot + KV pages are "
        "recycled; integer >= 1 (serving/broker.py GenerateServer)"),
    "MXNET_GENERATE_SLOTS": (
        "8", "honored",
        "batch-slot count of the continuous-batching decode program: "
        "the static batch dimension every decode step runs at; new "
        "requests are admitted into vacated slots every step; integer "
        ">= 1 (serving/generate.py GenerativePredictor)"),
    "MXNET_GENERATE_PAGE_SIZE": (
        "16", "honored",
        "tokens per KV-cache page: the paged allocator's block size — "
        "a finished request returns ceil(len/page_size) pages to the "
        "pool immediately; integer >= 1 (serving/generate.py)"),
    "MXNET_GENERATE_POOL_BYTES": (
        "0", "honored",
        "KV page-pool budget in bytes; 0 auto-sizes to slots x "
        "max-context pages (no oversubscription). A smaller explicit "
        "budget oversubscribes: admission backpressures on the typed "
        "PagePoolExhausted instead of OOMing; integer >= 0 "
        "(serving/generate.py)"),
    "MXNET_GENERATE_STREAM_FLUSH": (
        "8", "honored",
        "decode steps between stream_fn token flushes: generated "
        "tokens buffer per request and flush to the streaming "
        "callback every N steps (and at finish); integer >= 1 "
        "(serving/broker.py GenerateServer)"),
    # --- shared-prefix KV cache + speculative decoding (ISSUE 16) ---
    "MXNET_GENERATE_PREFIX_CACHE": (
        "0", "honored",
        "enable the shared-prefix KV cache: a radix index over full "
        "KV pages keyed by token-id page runs — admission matches the "
        "longest cached prefix, shares those pages copy-on-write via "
        "per-page refcounts and prefills only the uncovered tail; off "
        "(the default) is bit-identical to the unshared path; "
        "0/1/true/false (serving/broker.py GenerateServer)"),
    "MXNET_GENERATE_PREFIX_EVICT": (
        "0", "honored",
        "max KV pages the prefix index may pin; crossing the bound "
        "evicts least-recently-matched entries, and pool pressure "
        "evicts regardless (sharing never causes a PagePoolExhausted "
        "a no-sharing run would avoid); 0 = bounded only by pool "
        "pressure; integer >= 0 (serving/broker.py GenerateServer)"),
    "MXNET_GENERATE_SPEC_K": (
        "0", "honored",
        "speculative-decoding depth: the draft model proposes k "
        "tokens per slot per round and ONE batched verify step of the "
        "target model accepts the longest agreeing prefix (greedy "
        "token-for-token parity with non-speculative decode); 0 "
        "disables; integer >= 0 (serving/broker.py GenerateServer)"),
    "MXNET_GENERATE_DRAFT": (
        "0", "honored",
        "self-draft layer count for speculative decoding: the draft "
        "model is the target's FIRST N transformer layers sharing "
        "embed/pos/final-LN (models/transformer.py draft_from_layers); "
        "0 means an explicit draft_config=/draft_params= must be "
        "passed when MXNET_GENERATE_SPEC_K > 0; integer >= 0 "
        "(serving/broker.py GenerateServer)"),
    # --- sharded embeddings (ISSUE 14) ---
    "MXNET_EMBED_SHARDS": (
        "0", "honored",
        "row-shard count override for ShardedEmbeddingTable; 0 (the "
        "default) shards one-per-server, shard s lives on server "
        "s %% num_servers otherwise; integer >= 0 "
        "(embedding/table.py)"),
    "MXNET_EMBED_DEDUP": (
        "1", "honored",
        "deduplicate requested row ids before pulling (one row_pull "
        "frame per shard); 0 falls back to the naive per-id pull "
        "baseline the bench compares against; 0|1, anything else "
        "raises (embedding/table.py)"),
    "MXNET_EMBED_PULL_BATCH": (
        "65536", "honored",
        "pull batch budget: max rows per row_pull RPC frame — larger "
        "requests split into multiple frames per shard; integer >= 1 "
        "(embedding/table.py)"),
    "MXNET_EMBED_WIRE": (
        "raw", "honored",
        "row-gradient wire treatment for embedding scatter pushes: "
        "'raw' or '2bit' (the PR 4 packed two-bit quantizer applied "
        "to the pushed row block, with per-row error-feedback "
        "residuals held client-side for the rows this worker touched) "
        "(embedding/table.py)"),
    "MXNET_EMBED_WIRE_THRESHOLD": (
        "0.5", "honored",
        "ternary threshold for MXNET_EMBED_WIRE=2bit; finite float "
        "> 0 (embedding/table.py)"),
    # --- sharded data input (ISSUE 17) ---
    "MXNET_DATA_SHARDS": (
        "8", "honored",
        "default shard count for write_record_shards (capped at the "
        "record count so no shard is empty); integer >= 1 "
        "(data/writer.py)"),
    "MXNET_DATA_WORKERS": (
        "0", "honored",
        "background decode/augment process-pool size for "
        "ShardedRecordStream; 0 decodes inline on the reading thread; "
        "integer >= 0 (data/service.py)"),
    "MXNET_DATA_PREFETCH": (
        "2", "honored",
        "prefetch-queue depth (read/decode chunks buffered ahead of "
        "the training thread); 0 = fully synchronous reads, the bench "
        "baseline; integer >= 0 (data/service.py)"),
    "MXNET_DATA_DETERMINISTIC": (
        "1", "honored",
        "seed record decode/augment from (epoch, shard, record-index) "
        "so elastic shard rebalancing replays byte-identical batches; "
        "0 salts seeds with worker identity; 0|1, anything else "
        "raises (data/service.py)"),
    "MXNET_DATA_LEASE_TTL": (
        "30", "honored",
        "shard-lease time-to-live in seconds: a lease not renewed "
        "(cursor committed) within the TTL returns to the pool for "
        "rebalancing; finite float > 0 (tracker.py lease books, "
        "data/service.py local authority)"),
    # --- fleet autoscaling + multi-tenant QoS (ISSUE 18) ---
    "MXNET_FLEET_AUTOSCALE_INTERVAL": (
        "1.0", "honored",
        "autoscaler control-tick period in seconds; finite float > 0 "
        "(serving/autoscale.py)"),
    "MXNET_FLEET_AUTOSCALE_MIN": (
        "1", "honored",
        "floor on the fleet's desired replica count (scale-down never "
        "goes below it); integer >= 1, must be <= _MAX "
        "(serving/autoscale.py)"),
    "MXNET_FLEET_AUTOSCALE_MAX": (
        "4", "honored",
        "ceiling on the fleet's desired replica count; integer >= 1 "
        "(serving/autoscale.py)"),
    "MXNET_FLEET_AUTOSCALE_UP_LOAD": (
        "4.0", "honored",
        "mean queued+in-flight per serving replica at/above which a "
        "tick votes scale-up; finite float > 0 (serving/autoscale.py)"),
    "MXNET_FLEET_AUTOSCALE_DOWN_LOAD": (
        "0.5", "honored",
        "mean queued+in-flight per serving replica at/below which a "
        "tick votes scale-down; float >= 0, must be < _UP_LOAD — the "
        "gap between them is the anti-flap dead band "
        "(serving/autoscale.py)"),
    "MXNET_FLEET_AUTOSCALE_HYSTERESIS": (
        "3", "honored",
        "consecutive agreeing ticks required before a scale decision "
        "acts (flap guard); integer >= 1 (serving/autoscale.py)"),
    "MXNET_FLEET_AUTOSCALE_COOLDOWN": (
        "5.0", "honored",
        "seconds after a scale action during which further actions "
        "are held (counted as holds_cooldown); float >= 0 "
        "(serving/autoscale.py)"),
    "MXNET_FLEET_AUTOSCALE_SLO_MS": (
        "0", "honored",
        "serving p99 SLO in milliseconds: any serving replica at/"
        "above it makes the tick vote scale-up regardless of queue "
        "depth; 0 disables the latency signal; float >= 0 "
        "(serving/autoscale.py)"),
    "MXNET_QOS_TENANTS": (
        "", "honored",
        "per-tenant QoS spec 'name[:k=v,...];...' with keys prio|"
        "priority (latency|normal|bulk), req_rate (requests/s > 0), "
        "tok_rate (rows/s > 0); empty disables QoS; malformed raises "
        "naming this knob (serving/qos.py)"),
    "MXNET_QOS_DEFAULT_PRIORITY": (
        "normal", "honored",
        "priority class for requests with no tenant label or an "
        "unconfigured tenant: latency|normal|bulk (serving/qos.py)"),
    "MXNET_QOS_BURST_SECONDS": (
        "1.0", "honored",
        "token-bucket burst window: a tenant may burst rate*burst "
        "units above its steady rate; finite float > 0 "
        "(serving/qos.py)"),
    # --- tensor-parallel execution (ISSUE 20) ---
    "MXNET_MP_SIZE": (
        "1", "honored",
        "tensor-parallel ('mp') mesh-axis size for the fused SPMD step "
        "and the sharded serving group: the visible devices split into "
        "a (dp = N // mp) x mp mesh, so mp must divide the device "
        "count; 1 (the default) is bit-identical to the pure "
        "data-parallel path; integer >= 1 (parallel/mesh.py "
        "train_mesh, module/spmd_group.py, serving/predictor.py)"),
    "MXNET_MP_RULES": (
        "", "honored",
        "extra parameter-sharding rules 'regex:spec;regex:spec' where "
        "spec is a comma list with one entry per dim, each '*' "
        "(replicate that dim) or a mesh-axis name — e.g. "
        "'.*proj_weight:*,mp' column-shards the last dim over mp. "
        "Applied AFTER the transformer's built-in megatron rules; a "
        "matched rule that names a missing axis or does not divide "
        "the dim raises (no silent replication); malformed grammar "
        "raises naming this knob (parallel/spmd.py parse_rules, "
        "module/spmd_group.py)"),
    # --- misc ---
    "MXNET_TPU_NO_NATIVE": (
        "0", "honored", "force pure-Python fallbacks (_native.py)"),
    "MXNET_STORAGE_FALLBACK_LOG_VERBOSE": (
        "1", "accepted", "log dense fallbacks of sparse ops"),
}


def get(name, default=None):
    """Raw string value of a knob (env wins; then registry default)."""
    if name in os.environ:
        return os.environ[name]
    if default is not None:
        return default
    if name in KNOBS:
        return KNOBS[name][0]
    return None


def get_int(name, default=None):
    v = get(name, None if default is None else str(default))
    return int(v) if v not in (None, "") else None


def get_bool(name, default=False):
    v = get(name, "1" if default else "0")
    return str(v).strip().lower() in ("1", "true", "yes", "on")


# --- strict typed accessors (PR 6 convention: a malformed knob is a
# job misconfiguration — fail loudly at the read site, never train with
# a silently-substituted default) ------------------------------------
def get_strict_bool(name):
    """0/1/true/false/yes/no/on/off; anything else raises MXNetError."""
    from .base import MXNetError

    v = str(get(name)).strip().lower()
    if v in ("1", "true", "yes", "on"):
        return True
    if v in ("0", "false", "no", "off"):
        return False
    raise MXNetError("%s=%r must be a boolean (0|1)" % (name, get(name)))


def get_choice(name, choices):
    from .base import MXNetError

    v = str(get(name)).strip().lower()
    if v not in choices:
        raise MXNetError("%s=%r must be one of %s"
                         % (name, get(name), "|".join(choices)))
    return v


def get_nonneg_int(name):
    from .base import MXNetError

    raw = get(name)
    try:
        v = int(str(raw).strip())
    except (TypeError, ValueError):
        v = -1
    if v < 0:
        raise MXNetError("%s=%r must be an integer >= 0" % (name, raw))
    return v


def get_positive_int(name):
    from .base import MXNetError

    raw = get(name)
    try:
        v = int(str(raw).strip())
    except (TypeError, ValueError):
        v = 0
    if v < 1:
        raise MXNetError("%s=%r must be an integer >= 1" % (name, raw))
    return v


def get_nonneg_float(name):
    from .base import MXNetError

    raw = get(name)
    try:
        v = float(str(raw).strip())
    except (TypeError, ValueError):
        v = float("nan")
    if not 0.0 <= v < float("inf"):  # also rejects NaN
        raise MXNetError("%s=%r must be a finite float >= 0" % (name, raw))
    return v


def get_positive_float(name):
    from .base import MXNetError

    raw = get(name)
    try:
        v = float(str(raw).strip())
    except (TypeError, ValueError):
        v = float("nan")
    if not 0.0 < v < float("inf"):  # also rejects NaN
        raise MXNetError("%s=%r must be a finite float > 0" % (name, raw))
    return v


def describe():
    """[(name, current_value, status, description)] for every knob."""
    return [(n, get(n), s, d) for n, (_, s, d) in sorted(KNOBS.items())]


def print_summary():
    for name, value, status, desc in describe():
        print("%-40s %-10s %-8s %s" % (name, value, status, desc))
