"""Global flags plane — the gflags equivalent (reference:
paddle/utils/Flags.{h,cpp} DEFINE_bool/int32/string and the
``paddle.init(use_gpu=..., trainer_count=...)`` surface that forwarded
them).

Typed registry with three override layers, strongest last:
defaults < environment (``PADDLE_TPU_<NAME>``) < explicit ``set_flag`` /
``paddle.init(**kwargs)``.  Unknown names raise — the reference gflags
aborts on unknown flags the same way."""

from __future__ import annotations

import os
from typing import Any, Dict

_DEFS: Dict[str, tuple] = {}  # name -> (type, default, help)
_VALUES: Dict[str, Any] = {}

_ENV_PREFIX = "PADDLE_TPU_"


def define_flag(name: str, default, help_: str = "") -> None:
    """Register a flag.  Re-registering an existing name with the identical
    type+default is an idempotent no-op (module reloads); a CONFLICTING
    re-registration raises — the reference gflags aborts on duplicate
    DEFINE_* the same way.  (Silently letting the last definition win is
    how a plugin's `seed` flag used to steal the trainer's; the self-lint
    rule A204 catches the static cases, this guards the dynamic ones.)"""
    if name in _DEFS:
        old_type, old_default, _ = _DEFS[name]
        if old_type is not type(default) or old_default != default:
            raise ValueError(
                f"flag {name!r} is already defined with default "
                f"{old_default!r} ({old_type.__name__}); re-registering it "
                f"with default {default!r} ({type(default).__name__}) would "
                "silently change behavior — reuse the existing flag or "
                "pick a distinct name"
            )
    _DEFS[name] = (type(default), default, help_)


def _coerce(name: str, value):
    t = _DEFS[name][0]
    if t is bool and isinstance(value, str):
        return value.lower() in ("1", "true", "yes")
    return t(value)


def get_flag(name: str):
    if name not in _DEFS:
        raise KeyError(f"unknown flag {name!r}; defined: {sorted(_DEFS)}")
    if name in _VALUES:
        return _VALUES[name]
    env = os.environ.get(_ENV_PREFIX + name.upper())
    if env is not None:
        return _coerce(name, env)
    return _DEFS[name][1]


def set_flag(name: str, value) -> None:
    if name not in _DEFS:
        raise KeyError(f"unknown flag {name!r}; defined: {sorted(_DEFS)}")
    _VALUES[name] = _coerce(name, value)


def set_flags(**kwargs) -> None:
    for k, v in kwargs.items():
        set_flag(k, v)


def all_flags() -> Dict[str, Any]:
    return {name: get_flag(name) for name in _DEFS}


def reset_flags() -> None:
    _VALUES.clear()


# -- the reference flag set that still means something on TPU ---------------
# (Flags.cpp: use_gpu/trainer_count/log_period/show_parameter_stats_period/
#  seed/beam_size...; pserver networking flags are obsolete — the mesh
#  replaces them.)
define_flag("use_tpu", True, "accepted for surface compat; platform comes from jax")
define_flag("trainer_count", 1,
            "data-parallel width: `paddle-tpu train --trainer_count N` builds "
            "an N-way data mesh (or fails); library callers pass mesh= to "
            "trainer.SGD instead")
define_flag("seed", 0, "global RNG seed")
define_flag("log_period", 100, "log training stats every N batches")
define_flag("show_parameter_stats_period", 0, "log per-parameter stats every N batches (0=off)")
define_flag("beam_size", 5, "default generation beam width")
define_flag("check_nans", False, "enable jax nan-debugging (FP trap equivalent)")
define_flag("compute_dtype", "", "bfloat16 enables mixed precision")
define_flag("profile_dir", "", "write jax profiler traces here when set")
define_flag("use_bucketing", False,
            "length-bucketed feed for variable-length sequence workloads: "
            "the trainer/CLI batch readers route through reader.bucketing."
            "token_budget_batch (batch size scales inversely with bucket "
            "length, tokens/step ~constant) and the DataFeeder pads to the "
            "canonical 16*2^k shape ladder (core.batch.DEFAULT_LADDER) so "
            "jit recompiles stay bounded by the ladder size; reference v1 "
            "configs opt in via this flag with zero config edits")
define_flag("bucketing_token_budget", 0,
            "padded tokens per step for use_bucketing (0 = derive from the "
            "config batch size x the tallest ladder rung of the first "
            "window — the same padded token count the unbucketed feed "
            "would have spent per step)")
define_flag("scan_early_exit", True,
            "recurrent_group scans skip dead steps: when every row of a "
            "step is padding (the batch's true max length sits below the "
            "padded ladder rung), a lax.cond passes the carry through "
            "instead of running the step body — the compiled shape stays "
            "the rung's, the executed trip count shrinks to the bucket "
            "bound")
define_flag("fused_attention_gru", True,
            "recurrent_group decoder steps that match the v1 attention-GRU "
            "idiom (simple_attention + gru_step — the NMT decoder) lower "
            "onto the fused custom-VJP scan core (ops/rnn.py _attgru_core: "
            "state projection + GRU gates share one GEMM, the target-side "
            "input projection hoists out of the scan, weight grads are "
            "post-scan einsums) instead of the generic per-layer scan body; "
            "non-matching steps always use the generic path")
define_flag("cache_pass_in_mem", False,
            "device-resident pass cache (the TPU-native CacheType."
            "CACHE_PASS_IN_MEM, reference PyDataProvider2.cpp:69): epoch 1 "
            "captures every staged batch in its wire form (uint8 stays "
            "uint8 — ~1 byte/px of HBM; normalize stays fused in the step) "
            "and every later epoch replays it from HBM with a reproducible "
            "on-device jax.random.permutation shuffle — zero H2D traffic, "
            "repeat-epoch training goes compute-bound.  @provider(cache="
            "CacheType.CACHE_PASS_IN_MEM) configs opt in with zero edits; "
            "this flag forces it for any reader")
define_flag("data_echo_factor", 1,
            "train each epoch-1 batch N times back-to-back (data echo) so "
            "the H2D-bound first epoch amortizes every transfer N-fold; "
            "1 = off.  Applies whenever the pass cache is enabled")
define_flag("pass_cache_hbm_budget_mb", 4096,
            "PER-DEVICE HBM budget for the device-resident pass cache; a "
            "pass that does not fit falls back to streaming with a "
            "warning.  Sizing rule: budget >= n_samples x bytes_per_sample "
            "in wire form / data-axis size (uint8 224x224x3 ~ 0.15 "
            "MB/image; a batch sharded over n chips counts its largest "
            "per-device shard)")
define_flag("divergence_sentinel", True,
            "fold a device-side finiteness check of loss + gradient global-"
            "norm into the jitted train step (robustness/): one fused "
            "scalar health flag rides the step's metric outputs, and a "
            "non-finite step is SKIPPED on device (params/opt-state pass "
            "through unchanged) instead of corrupting the run.  The flag "
            "costs one norm reduction per step and no extra host sync")
define_flag("sentinel_skip_limit", 3,
            "consecutive device-skipped (non-finite) steps that declare "
            "divergence and trigger rollback (robustness.recovery)")
define_flag("sentinel_ema_decay", 0.98,
            "decay of the healthy-loss EMA the spike detector compares "
            "against")
define_flag("sentinel_spike_factor", 4.0,
            "a fetched cost above spike_factor x EMA counts as a loss "
            "spike; sentinel_spike_patience consecutive spikes declare "
            "divergence even when every value is finite")
define_flag("sentinel_spike_patience", 3,
            "consecutive EMA spikes before the sentinel declares "
            "divergence")
define_flag("num_sanitizer", False,
            "arm the divergence-localizing numerics sanitizer "
            "(analysis/num_sanitizer.py; env PADDLE_TPU_NUM_SANITIZER "
            "reaches subprocesses): the trainer host-copies each step's "
            "inputs pre-dispatch, and a sentinel-flagged step is re-"
            "executed eqn-by-eqn to name the first non-finite-producing "
            "op (layer + source provenance, input max-abs stats under "
            "StatSet num/<eqn>) in a flight-recorder postmortem.  "
            "Capture costs one host copy per step — debug drills only; "
            "unarmed the train path is untouched")
define_flag("failure_max", 3,
            "rollback retries of the same data window before it is "
            "quarantined and training continues past it — the go/master "
            "processFailedTask discipline (service.go:308) applied to "
            "training-state recovery")
define_flag("checkpoint_period_batches", 50,
            "full-state checkpoint cadence (in batches) when the trainer "
            "runs with checkpoint_dir; each checkpoint is the rollback "
            "anchor AND the preemption/kill -9 resume point, and bounds "
            "the replay window retained on device")
define_flag("chaos", "",
            "chaos fault-point spec, e.g. 'nan_batch@5,kill@12' "
            "(robustness/chaos.py; env PADDLE_TPU_CHAOS reaches "
            "subprocesses) — NEVER set in production")
define_flag("rpc_max_message_mb", 64,
            "hard bound (MB) on one master-RPC wire frame, enforced on "
            "send AND recv (master_wire.py): an over-budget outbound "
            "payload — a too-large gradient tree — fails fast with a "
            "structured WireOversizeError instead of wedging against a "
            "frozen peer's full socket buffer, and an over-budget INBOUND "
            "length prefix is refused before allocation, so a hostile or "
            "damaged frame can never balloon the master's heap")
define_flag("serving_max_slots", 8,
            "in-flight sequence capacity of the serving plane "
            "(serving/engine.py): the continuous-batching decode step is "
            "compiled per slot-count LADDER RUNG up to this many live "
            "sequences; requests beyond it queue")
define_flag("serving_block_tokens", 16,
            "tokens per HBM block of the block-paged decode-state cache "
            "(serving/pages.py).  Must divide the base shape-ladder rung "
            "(16) so every padded source extent splits into whole blocks "
            "and the gathered attention extent stays a ladder rung "
            "(decode outputs bit-identical to the one-shot path)")
define_flag("serving_hbm_budget_mb", 64,
            "PER-DEVICE HBM budget for the block-paged serving cache — "
            "the PR-3 pass-cache accounting discipline applied to decode "
            "state: capacity = budget // bytes_per_block, exhaustion is a "
            "REFUSED admission (request waits in queue), never an OOM.  "
            "Sizing rule: bytes_per_block = block_tokens x (enc 2H + "
            "proj H) x dtype_bytes; a request of S source tokens holds "
            "ceil(S/block_tokens) blocks while in flight")
define_flag("serving_decode_block_steps", 4,
            "tokens decoded per compiled dispatch in the serving plane — "
            "the K-steps-per-dispatch amortization (trainer "
            "make_multi_train_step discipline) applied to decode: an "
            "inner lax.scan emits K tokens per host sync, multiplying "
            "dispatch-bound throughput ~K-fold; admission/retirement "
            "quantize to K-token boundaries (finished rows clamp to EOS "
            "in-graph, so outputs stay bit-identical to the one-shot "
            "path).  1 = sync every token (lowest time-to-first-token)")
define_flag("serving_prefix_cache", False,
            "copy-on-write prefix sharing in the serving plane "
            "(serving/engine.py): finished prompts park their encoder "
            "pages in a refcount-0 LRU pool keyed on token-block hashes + "
            "the engine's topology fingerprint; a request whose FULL "
            "prompt matches maps the same blocks into its page table with "
            "ZERO prefill dispatches (bit-identical — the bi-GRU encoder "
            "reads the whole prompt, so only exact-prompt reuse is sound; "
            "chunked prefills additionally resume mid-prompt from cached "
            "forward-GRU carries).  Blocks free only at refcount 0; "
            "eviction is LRU under the same serving_hbm_budget_mb")
define_flag("serving_spec_decode", False,
            "speculative decoding in the serving plane: an n-gram draft "
            "proposes serving_decode_block_steps tokens and the target "
            "model verifies ALL of them in ONE dispatch (the existing "
            "K-steps compiled shape, drafts as inputs); the emitted "
            "tokens are exactly the greedy argmax chain's — acceptance "
            "only changes how many land per dispatch, never their values "
            "(rejection falls back bit-identically).  Accepted-token "
            "rate rides serving metrics as spec_accept_rate")
define_flag("serving_spec_ngram", 2,
            "context n-gram order of the serving draft proposer: the last "
            "n generated tokens are matched against the request's own "
            "generated history and the continuation after the most recent "
            "match is proposed (prompt-lookup decoding); larger n = "
            "fewer, more precise matches")
define_flag("serving_default_deadline_s", 0.0,
            "default end-to-end deadline (seconds from submit) stamped on "
            "serving requests that carry none of their own; the scheduler "
            "SHEDS a request whose predicted queue wait already blows its "
            "deadline (distinct 'shed' status — at overload the plane "
            "degrades to its SLO-feasible subset instead of collapsing "
            "into universal timeouts) and cancels a live request once its "
            "deadline passes (pages free immediately).  0 = no deadline "
            "(pre-SLO behavior)")
define_flag("serving_queue_limit", 0,
            "bound on requests queued ahead of admission (submitted + "
            "validated-waiting) in the serving scheduler: a submit beyond "
            "it is REJECTED immediately ('rejected: queue full' — open-"
            "loop backpressure, the client retries elsewhere) instead of "
            "growing an unbounded queue whose every occupant times out.  "
            "0 = unbounded (pre-SLO behavior)")
define_flag("serving_prefill_chunk_tokens", 0,
            "chunked prefill: a prompt whose padded source extent exceeds "
            "this many tokens prefills in ladder-rung chunks (carried "
            "bi-GRU state, one bounded dispatch per chunk) interleaved "
            "with decode steps, so a long prompt no longer stalls every "
            "decoding sequence for its whole encoder forward (head-of-"
            "line isolation; outputs stay bit-identical to the one-shot "
            "path).  Must be a multiple of serving_block_tokens and "
            "divide every larger shape-ladder rung.  0 = off (whole-"
            "prompt prefill)")
define_flag("scenario_slo_ms", 0.0,
            "end-to-end latency SLO for the scenario harness "
            "(robustness/scenarios.py): goodput counts requests completed "
            "within this many ms of submit, and per-request deadlines "
            "default to it.  0 = derive from the measured saturation "
            "wave (2.5x its p95 service time, floored at 50 ms)")
define_flag("serving_max_new_tokens", 32,
            "default per-request decode cap of the serving plane (a "
            "request's own max_new_tokens overrides; the generator's "
            "max_length stays the compiled ceiling)")
define_flag("serving_priority_aging_s", 2.0,
            "aging rate of the strict-priority-with-aging dequeue "
            "(serving/scheduler.py): every this-many seconds of queue "
            "wait promote a waiting request one priority level, so "
            "batch-class traffic ages into urgency instead of starving "
            "behind a steady interactive stream; 0 = pure strict "
            "priority (starvation becomes the operator's choice)")
define_flag("serving_class_deadline_s", "",
            "per-class default end-to-end deadlines, 'prio:seconds' "
            "pairs e.g. '0:0.25,2:1.5' (priority 0 is most urgent): a "
            "request of that class submitted without its own deadline "
            "gets the class default; unlisted classes fall back to "
            "serving_default_deadline_s")
define_flag("serving_class_shed_slack", "",
            "per-class multiplier on the shed predictor's service-"
            "safety headroom, 'prio:factor' pairs e.g. '2:2.0': >1 "
            "sheds that class EARLIER under pressure (more headroom "
            "demanded), <1 lets it gamble closer to its deadline; "
            "unlisted classes use 1.0")
define_flag("trace_dir", "",
            "obs plane (paddle_tpu/obs/): arm Chrome-trace export — every "
            "process dumps its span timeline to trace-<role>-<pid>.json "
            "under this directory at exit, and flight-recorder postmortems "
            "land here too.  `paddle-tpu trace merge --dir D` zips the "
            "per-process files into ONE Perfetto-loadable timeline "
            "(clock-skew aligned via the RPC plane's request/response "
            "pairs).  Env PADDLE_TPU_TRACE_DIR reaches subprocess fleets; "
            "empty = no export (the flight-recorder ring still records)")
define_flag("flight_recorder", True,
            "keep the obs span recorder armed at bounded memory (per-"
            "thread rings of trace_ring_events events): SIGUSR1, a firing "
            "chaos point, the divergence sentinel, and the serving "
            "scheduler's crash guard dump the last events to "
            "flight-<pid>.json (under trace_dir, else the system temp "
            "dir) — postmortem timelines survive a kill -9 fleet drill.  "
            "Overhead is gated <= 3% by bench_tracing_overhead; off = "
            "every emit is one attribute read")
define_flag("trace_ring_events", 4096,
            "bounded ring capacity (events) of each thread's obs span "
            "buffer — the flight recorder's memory ceiling is "
            "threads x this x ~100 bytes")
define_flag("metrics_out", "",
            "obs metrics export: periodically snapshot the StatSet plane "
            "+ the registered SLO gauges (serving queue depth, pages in "
            "use, EWMA predicted wait, served/shed/rejected/timeout "
            "ledger) to this file in Prometheus text exposition format "
            "(atomic replace).  Empty = off")
define_flag("metrics_port", 0,
            "serve the same Prometheus exposition on "
            "http://127.0.0.1:<port>/metrics (0 = no endpoint; the "
            "localhost bind is deliberate — this is a scrape surface, "
            "not an API)")
define_flag("metrics_period_s", 5.0,
            "seconds between metrics_out snapshots")
define_flag("use_pallas_attention", False,
            "take the blocked attention kernels (ops/pallas_attention.py) "
            "BELOW 1,024 keys too, where queries and keys are equally many "
            "on a TPU: from 1,024 keys on multi_head_attention takes them "
            "whatever this says (there they are 2-4x faster than the dense "
            "path and keep no [T,T] scores in HBM); below, the dense path "
            "is as fast or faster without the causal mask (512 keys: 0.57 "
            "against 0.74 ms a layer) and slower with it (1.30 against "
            "0.70), so this stays the user's call there")
define_flag("quantized_allreduce", False,
            "block-scaled quantized gradient allreduce (ops/quantize.py "
            "quantized_psum): the data-axis gradient psum rides as an "
            "int8/bf16 payload psum with its f32 block-scale psum beside "
            "it (the N405 structure), cutting per-step allreduce bytes "
            "~4x (EQuARX, arXiv:2506.17615).  OFF (default) keeps the "
            "implicit f32 psum — bit-identical to every prior trajectory")
define_flag("quantize_block_size", 256,
            "elements per block of the block-scaled quantization format "
            "(one f32 max-abs scale per block; shared by the in-graph "
            "allreduce, the elastic wire contributions, and int8 serving "
            "weights).  Smaller blocks track local dynamic range tighter "
            "at more scale overhead (4 bytes per block)")
define_flag("quantize_payload_dtype", "int8",
            "payload dtype of the quantized allreduce: 'int8' (1 "
            "byte/element, rounded into [-127,127]) or 'bfloat16' (2 "
            "bytes/element, no rounding step beyond the bf16 mantissa)")
define_flag("quantize_stochastic_rounding", False,
            "stochastic rounding for int8 quantized-allreduce payloads "
            "(floor(v + u), u~U[0,1), per-shard decorrelated): unbiased "
            "in expectation, trades per-step noise for zero systematic "
            "rounding drift over a long run")
define_flag("elastic_quantized_grads", False,
            "elastic workers submit per-task gradient contributions as "
            "block-scaled (int8 blocks, f32 scales) typed arrays on the "
            "master wire (ops/quantize.py quantize_tree) — ~4x fewer "
            "result-plane bytes per pass; reduce_results dequantizes "
            "BEFORE the sorted-order reduction, so the deterministic-"
            "trajectory contract is unchanged (all workers reduce the "
            "same dequantized bytes).  Env "
            "PADDLE_TPU_ELASTIC_QUANTIZED_GRADS reaches worker "
            "subprocesses")
define_flag("serving_int8_weights", False,
            "weight-only int8 serving decode: the fused decode-weight "
            "bundle's dense matrices live as int8 blocks + f32 scales "
            "and dequantize in-graph per dispatch (~4x smaller resident "
            "weight bytes under serving_hbm_budget_mb -> more concurrent "
            "slots per GB); biases/vectors stay f32, training is "
            "untouched (the certify_precision_plan weight-only ACCEPT "
            "case)")
define_flag("serving_int8_drift_budget", 0.08,
            "max tolerated per-step drift of int8-weight decode vs the "
            "f32 reference, measured as max|logits_int8 - logits_f32| / "
            "max|logits_f32| on a probe batch — the explicit bit-drift "
            "budget the serving bench and tests gate on")
define_flag("router_lease_timeout_s", 2.0,
            "heartbeat-lease timeout of the serving-fleet router's "
            "engine registry (serving/router.py — the master cluster "
            "plane's worker-lease discipline lifted to the serving "
            "tier): an engine silent this long is pruned and its "
            "in-flight requests re-route to the survivors")
define_flag("router_queue_limit", 0,
            "bound on requests concurrently inside the router's "
            "admission/dispatch section (the serving_queue_limit "
            "semantics one tier up): past it a request is REJECTED at "
            "the frontend before paying a network hop; 0 = unbounded")
define_flag("router_stats_poll_s", 0.2,
            "period of the router's per-engine stats poll — one typed "
            "RPC per engine per period (scheduler.export_stats over the "
            "wire codec, not a Prometheus scrape); routing scores read "
            "the latest snapshot")
define_flag("router_affinity", True,
            "prefix/session affinity routing in the fleet router: hash "
            "the request's session id (or its prefix block-chain key) "
            "to a preferred engine by rendezvous hashing, so "
            "shared-prefix traffic concentrates where the COW prefix "
            "cache already holds the blocks.  The preferred engine is "
            "OVERRIDDEN when its predicted wait exceeds the best "
            "engine's by more than router_affinity_slack_s — affinity "
            "must never defeat load balance")
define_flag("router_affinity_slack_s", 0.25,
            "how much worse (seconds of predicted wait) the affinity-"
            "preferred engine may be before the router falls back to "
            "the least-predicted-wait choice")
define_flag("router_call_timeout_s", 120.0,
            "per-request deadline of the router->engine serve RPC "
            "(dial + full decode + reply); requests carrying their own "
            "SLO use min(remaining deadline + grace, this)")
