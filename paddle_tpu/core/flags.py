"""Global flags system.

TPU-native analogue of Paddle's exported gflags (reference:
paddle/fluid/platform/flags.cc — 56 PADDLE_DEFINE_EXPORTED_* flags — and the
Python accessors get_flags/set_flags in python/paddle/fluid/framework.py via
pybind/global_value_getter_setter.cc). Flags are definable in-process,
overridable from the environment as FLAGS_<name>, and readable/settable at
runtime.
"""
from __future__ import annotations

import os
from typing import Any, Dict

_registry: Dict[str, dict] = {}


def define_flag(name: str, default: Any, doc: str = "", writable: bool = True):
    if name.startswith("FLAGS_"):
        name = name[len("FLAGS_") :]
    env = os.environ.get("FLAGS_" + name)
    value = default
    if env is not None:
        value = _parse(env, default)
    _registry[name] = {
        "value": value,
        "default": default,
        "doc": doc,
        "writable": writable,
    }
    return value


_TRUE_WORDS = frozenset(("1", "true", "yes", "on", "y", "t"))
_FALSE_WORDS = frozenset(("0", "false", "no", "off", "n", "f", ""))


def _parse(text: str, default):
    if isinstance(default, bool):
        # strict both ways: "0"/"off"/"no" are False, "1"/"on"/"yes" are
        # True, anything else is an error instead of silently False
        word = text.strip().lower()
        if word in _TRUE_WORDS:
            return True
        if word in _FALSE_WORDS:
            return False
        raise ValueError(
            f"invalid boolean flag value {text!r}: use 1/0, true/false, "
            "yes/no, or on/off"
        )
    if isinstance(default, int):
        return int(text)
    if isinstance(default, float):
        return float(text)
    return text


def _norm(name: str) -> str:
    return name[len("FLAGS_") :] if name.startswith("FLAGS_") else name


def get_flags(flags):
    """paddle.get_flags — accepts a name or list of names."""
    single = isinstance(flags, str)
    names = [flags] if single else list(flags)
    out = {}
    for n in names:
        key = _norm(n)
        if key not in _registry:
            raise ValueError(f"unknown flag {n!r}")
        out["FLAGS_" + key] = _registry[key]["value"]
    return out


def set_flags(flags: Dict[str, Any]):
    """paddle.set_flags — {'FLAGS_name': value, ...}."""
    for n, v in flags.items():
        key = _norm(n)
        if key not in _registry:
            raise ValueError(f"unknown flag {n!r}")
        entry = _registry[key]
        if not entry["writable"]:
            raise ValueError(
                f"flag FLAGS_{key} is read-only at runtime: it is consumed "
                "once at startup — export FLAGS_" + key + "=... in the "
                "environment before importing paddle_tpu instead"
            )
        if isinstance(v, str) and not isinstance(entry["default"], str):
            # env-style string values parse with the same (strict) rules as
            # FLAGS_* environment variables, so "0"/"off" mean False here too
            v = _parse(v, entry["default"])
        entry["value"] = v


def flag(name: str):
    return _registry[_norm(name)]["value"]


def describe_flags(match: str = None):
    """Sorted [{name, value, default, doc, writable}] for every registered
    flag, optionally filtered by a substring of the name (reference: the
    --help text gflags generates; used by tools/graph_lint.py to print the
    analysis-related flags in effect)."""
    out = []
    for name in sorted(_registry):
        if match is not None and match not in name:
            continue
        e = _registry[name]
        out.append({
            "name": "FLAGS_" + name,
            "value": e["value"],
            "default": e["default"],
            "doc": e["doc"],
            "writable": e["writable"],
        })
    return out


# ---------------------------------------------------------------------------
# Core flags (subset of reference platform/flags.cc relevant on TPU)
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False, "scan op outputs for NaN/Inf (debug mode)")
define_flag("benchmark", False, "sync after each op and record timings")
define_flag("eager_op_jit", True, "wrap per-op lowering in jax.jit with a compile cache")
define_flag(
    "eager_tape_jit", True,
    "compile the whole eager backward sweep into one cached XLA program",
)
define_flag(
    "eager_lazy_dispatch", False,
    "defer eager ops onto a pending per-thread segment and flush whole "
    "segments as ONE jitted program at materialization points (host reads, "
    "backward, device.synchronize); cached by segment signature",
)
define_flag(
    "eager_jit_cache_size", 4096,
    "LRU cap on the per-op jit / vjp compile caches and the lazy-dispatch "
    "output-aval metadata cache (0 = unbounded); oldest entries evict "
    "first, compile-cache evictions are counted",
)
define_flag(
    "eager_segment_cache_size", 256,
    "LRU cap on the lazy-dispatch segment compile cache (0 = unbounded)",
)
define_flag(
    "eager_segment_max_ops", 256,
    "flush a pending lazy-dispatch segment once it reaches this many ops "
    "(bounds trace length and compile time of one fused segment)",
)
define_flag(
    "eager_step_capture", True,
    "whole-step capture-and-replay under FLAGS_eager_lazy_dispatch: once a "
    "steady-state train step (fused forward segment + compiled-tape backward "
    "+ fused optimizer) repeats with an identical signature for "
    "FLAGS_eager_capture_warmup steps, re-trace the whole step as ONE XLA "
    "program with parameters and optimizer state donated in place; any "
    "signature mismatch / hook / retain_graph falls back to the 3-segment "
    "path with identical numerics",
)
define_flag(
    "eager_capture_donate", True,
    "donate parameter and optimizer-state buffers to the captured "
    "whole-step executable (in-place HBM reuse, the compile_train_step "
    "discipline). On backends with real donation (TPU/GPU) this "
    "invalidates stale aliases of the PREVIOUS buffers — e.g. a Tensor "
    "from p.detach() or an optimizer state_dict() held across a later "
    "captured step; set to 0 to keep whole-step capture (still 1 program "
    "per step) without buffer donation",
)
define_flag(
    "eager_capture_sharded", True,
    "mesh-aware whole-step capture: when the armed step's parameters carry "
    "multi-device NamedShardings, the captured program is jitted with "
    "in_shardings/out_shardings derived from parallel.sharding param/state "
    "specs and the same donation discipline as ShardedTrainStep — one "
    "donated multi-chip program per step. Donation additionally requires "
    "the analysis.sharding per-shard donation_safety proof for EVERY "
    "donated position (unproven positions replay non-donated, counted in "
    "capture_donation_fallbacks). Set to 0 to pin capture to the "
    "single-chip contract (sharded params then capture without declared "
    "shardings)",
)
define_flag(
    "eager_capture_warmup", 2,
    "number of consecutive identical steady-state steps observed before the "
    "whole-step capture controller captures and replays the step as one "
    "donated program",
)
define_flag(
    "eager_capture_cache_size", 8,
    "LRU cap on captured whole-step executables (0 = unbounded); evictions "
    "are counted in paddle.profiler.dispatch_counters()",
)
define_flag(
    "eager_async_compile", True,
    "move fresh XLA compiles off the Python hot path: the FIRST flush of a "
    "new lazy-segment signature executes its op plan eagerly (bitwise the "
    "same programs) while the fused segment program compiles on a "
    "background thread, and the first armed whole-step capture resolves on "
    "the 3-program path while its donated executable compiles off-thread; "
    "the next occurrence of the same signature joins the finished compile "
    "(compile-thread exceptions re-raise there with their original "
    "traceback). Numerics are identical; only host blocking time moves — "
    "see trace/compile/replay timers in paddle.profiler.dispatch_counters()",
)
define_flag(
    "pallas_fused_update", False,
    "route the fused optimizer update (optimizer.make_fused_update — the "
    "one shared definition used by the eager fused step AND the captured "
    "whole-step trace) through the hand-written Pallas TPU kernel for "
    "Adam / SGD / Momentum: each parameter's whole elementwise update "
    "chain plus its non-finite sentinel contribution runs as one kernel "
    "pass (one read + one write per buffer) instead of an XLA elementwise "
    "chain; programs-per-step stays 1 under capture. Off-TPU, and for "
    "unsupported rules/dtypes, the lax composition is used unchanged",
)
define_flag(
    "pallas_update_interpret", False,
    "run the Pallas fused-update kernel in interpreter mode so the kernel "
    "path is testable on CPU (slow; parity/debugging only)",
)
define_flag(
    "check_programs", 0,
    "run the paddle_tpu.analysis verifier over every program at compile "
    "time (Executor.run) and at lazy-segment flush: 0 = off, 1 = report "
    "every diagnostic as a Python warning, 2 = additionally raise "
    "ProgramVerificationError on error-severity findings",
)
define_flag(
    "memory_budget_mb", 0.0,
    "estimated peak-HBM budget (MB) enforced by the paddle_tpu.analysis "
    "memory_budget pass: when > 0, every checked program gets a static "
    "liveness-based peak estimate and an error-severity diagnostic when it "
    "exceeds the budget (0 = only the detected device HBM bounds apply); "
    "combine with FLAGS_check_programs to warn (1) or raise (2) at "
    "Executor.run compile time and lazy-segment flush",
)
define_flag(
    "comm_ratio_warn", 0.0,
    "comm/compute threshold (bytes on wire per flop) for the "
    "paddle_tpu.analysis collective_cost pass: when > 0, a checked sharded "
    "program whose ring-ICI wire bytes divided by estimated flops exceeds "
    "this ratio gets a warning-severity diagnostic naming the heaviest "
    "collective (0 = report the ratio informationally, never warn); "
    "combine with FLAGS_check_programs to surface it at build time",
)
define_flag(
    "memory_plan", "",
    "turn the memory_budget liveness estimate into an optimizer "
    "(paddle_tpu.analysis.plan): 'auto' makes the whole-step capture trace "
    "and jit.compile_train_step build a rematerialization plan whenever "
    "FLAGS_memory_budget_mb > 0 — the forward is sliced into planner-chosen "
    "jax.checkpoint stages so the step's estimated peak HBM fits the "
    "budget, recomputing only the slices peak-liveness demands (bitwise-"
    "identical numerics; a failed plan build falls back to the unplanned "
    "step as a counted reason). Empty (default) = plans are only built "
    "when explicitly requested (graph_lint --plan, plan_remat())",
)
define_flag(
    "offload_overhead_pct", 1.0,
    "measured-overhead budget (% of step time) for the optimizer host-"
    "offload scheduler (paddle_tpu.optimizer.offload): cold accumulator "
    "groups are parked in host memory between their update reads, and the "
    "scheduler shrinks/regrows the offloaded set from blocked-transfer "
    "EMAs so the prefetch stall it adds to a step stays under this budget "
    "(the CheckFreq tune-to-a-measured-budget discipline, like "
    "FLAGS_ckpt_overhead_pct)",
)
# ---------------------------------------------------------------------------
# Resilience runtime (paddle.resilience — see RESILIENCE.md)
# ---------------------------------------------------------------------------
define_flag(
    "fault_inject", "",
    "deterministic fault-injection spec for the resilience chaos harness, "
    "e.g. 'execute:p=0.2,compile:step>=3,nan:grads' — comma-separated "
    "clauses of kind (execute/compile/hang/nan/kill) with p=/step>=/x= "
    "qualifiers and an optional site target; decisions are seeded per "
    "(clause, site, step) from FLAGS_fault_seed so failures replay exactly "
    "(empty = off)",
)
define_flag(
    "fault_seed", 0,
    "seed for the fault-injection harness's per-(clause, site, step) "
    "decisions — same seed, same spec: same faults at the same steps",
)
define_flag(
    "fault_hang_ms", 20.0,
    "stall duration of an injected 'hang' fault before the simulated "
    "watchdog raises (classified transient, so the retry path runs)",
)
define_flag(
    "retry_max", 2,
    "max retries of a transiently-failed program launch (per-op, segment "
    "flush, backward, optimizer update, captured replay) or checkpoint "
    "write before the error propagates; 0 disables retrying",
)
define_flag(
    "retry_backoff_ms", 5.0,
    "base delay of the capped exponential retry backoff (doubles per "
    "attempt, multiplied by up to 25% jitter); accumulated delay is "
    "counted in dispatch_counters()['retry_backoff_ms']",
)
define_flag(
    "retry_backoff_max_ms", 1000.0,
    "cap on a single retry backoff delay",
)
define_flag(
    "ladder_demote_after", 2,
    "faults observed at an execution tier (captured / lazy) before the "
    "degradation ladder demotes it one rung (captured→lazy→per-op); "
    "numerics are identical across rungs, only programs-per-step changes",
)
define_flag(
    "ladder_cooldown_steps", 8,
    "clean steps a demoted tier waits before the ladder re-promotes it "
    "and the fast path is attempted again",
)
define_flag(
    "numeric_rescue", "",
    "step-level numeric rescue policy: '' (off), 'skip' (drop steps with "
    "non-finite gradients; params/optimizer state untouched), 'lr_backoff' "
    "(skip + multiply lr by FLAGS_numeric_rescue_lr_factor), or 'abort' "
    "(raise FloatingPointError). Detection is a sentinel fused into the "
    "optimizer-update / captured-step program — no extra program launches",
)
define_flag(
    "numeric_rescue_lr_factor", 0.5,
    "lr multiplier applied by the 'lr_backoff' numeric-rescue policy on "
    "each rescued step",
)
# ---------------------------------------------------------------------------
# Checkpointing (paddle.distributed.checkpoint — CheckFreq cadence tuning
# and snapshot pipelining; RESILIENCE.md "Checkpointing" section)
# ---------------------------------------------------------------------------
define_flag(
    "ckpt_overhead_pct", 3.5,
    "checkpoint-overhead budget (percent of steady-state compute) the "
    "auto-tuned cadence targets: with save_freq='auto' the CadenceTuner "
    "measures step time and the on-step-path snapshot cost, then picks the "
    "largest save frequency whose overhead stays under this budget "
    "(CheckFreq's ~3.5% discipline), re-tuning when step time drifts",
)
define_flag(
    "ckpt_async", True,
    "pipeline checkpoint persistence with compute: AsyncCheckpointer.save "
    "takes only a fast on-device snapshot of params + optimizer "
    "accumulators at the step boundary (bitwise the boundary state, taken "
    "before the next donated captured step can consume those buffers) and "
    "runs the device->host transfer + serialization + two-phase commit on "
    "a background thread overlapping the following steps; 0 restores the "
    "fully synchronous on-step-path save",
)
define_flag(
    "ckpt_cadence_max", 1000,
    "cap on the save frequency (steps between checkpoints) the auto "
    "cadence tuner may pick — bounds worst-case lost work when the "
    "snapshot is very cheap relative to the step",
)
define_flag(
    "ckpt_retune_pct", 25.0,
    "percent drift of the step-time EMA from its value at the last tune "
    "that triggers the cadence tuner to re-pick save_freq (e.g. after a "
    "degradation-ladder demotion changes steady-state step time)",
)
# ---------------------------------------------------------------------------
# Runtime observability (paddle.profiler.trace — see OBSERVABILITY.md)
# ---------------------------------------------------------------------------
define_flag(
    "trace_ring_size", 4096,
    "capacity of the flight recorder — the bounded in-memory ring of "
    "structured runtime events (paddle.profiler.trace) emitted at the "
    "execution choke points: program launches, segment flushes with their "
    "reasons, capture build/replay/fallback, async-compile submits/joins, "
    "retries and faults, ladder demotions, serving request phases, and "
    "checkpoint pipeline phases. Default on; 0 disables emission entirely "
    "(the off-mode fast path is one dict read per would-be event)",
)
define_flag(
    "trace_stall_ms", 0.0,
    "step-stall watchdog threshold: when > 0, a background watchdog "
    "observes the step heartbeat (resilience.runtime.on_step_end) and — if "
    "no step boundary lands for this many ms — emits a 'stall' event and "
    "dumps a crash postmortem (FLAGS_postmortem_dir). One postmortem per "
    "stall episode; the next completed step re-arms it. 0 = off",
)
define_flag(
    "postmortem_dir", "",
    "directory for crash postmortems: unrecovered faults, Preempted, "
    "ProgramVerificationError, and step-stall watchdog trips dump a JSON "
    "file here with the flight recorder's event tail, the unified metrics "
    "snapshot (dispatch counters included), a live-buffer memory snapshot, "
    "and the resilience/ladder state. Empty = postmortems disabled",
)
define_flag(
    "postmortem_events", 256,
    "number of trailing flight-recorder events included in each postmortem "
    "dump (the event tail that explains what led up to the crash)",
)
define_flag(
    "postmortem_keep", 32,
    "bound on the number of postmortem JSON files kept in "
    "FLAGS_postmortem_dir: every dump prunes the OLDEST dumps past this "
    "count (a flapping sentinel or a rescue storm cannot grow the "
    "directory without limit); pruned files are counted in "
    "dispatch_counters()['postmortems_pruned'] and reported by the "
    "/postmortems diagnostics endpoint. 0 = unbounded",
)
# ---------------------------------------------------------------------------
# Attribution layer (paddle.profiler.attribution — see OBSERVABILITY.md
# "Attribution & triage")
# ---------------------------------------------------------------------------
define_flag(
    "telemetry", False,
    "fused numerics telemetry (paddle.profiler.attribution): the fused "
    "optimizer update (and the captured whole-step program) computes one "
    "extra stacked vector output — per-parameter grad-norm, param-norm, "
    "and update-norm sums of squares — inside the SAME traced program "
    "(zero extra device launches; programs-per-step stays 13/3/1 per "
    "tier, and step numerics are bitwise-identical to telemetry-off). "
    "The host reads the vector each step into per-group gauges "
    "(telemetry_* metric families), a bounded history ring "
    "(FLAGS_telemetry_history) the triage postmortems dump, and one "
    "'telemetry' flight event per step. Off by default: reading the "
    "vector synchronizes with the step program on the host",
)
define_flag(
    "telemetry_history", 64,
    "per-step telemetry records kept in the attribution history ring — "
    "the 'last N telemetry vectors' a triage postmortem includes so an "
    "out-of-trend parameter group is visible in context",
)
define_flag(
    "telemetry_spike_factor", 10.0,
    "a parameter group whose grad-norm exceeds this multiple of its own "
    "EMA (or goes non-finite) is recorded as a telemetry spike: counted "
    "(telemetry_spikes + the telemetry_spike_groups labeled family), "
    "named in the per-step telemetry flight event, and listed first in "
    "the postmortem triage section",
)
# ---------------------------------------------------------------------------
# Ops plane (paddle.profiler.diag / paddle.profiler.sentinel — see
# OBSERVABILITY.md "Ops plane")
# ---------------------------------------------------------------------------
define_flag(
    "diag_port", -1,
    "per-process diagnostics HTTP server (paddle.profiler.diag): the port "
    "diag.start() binds its stdlib ThreadingHTTPServer daemon to, serving "
    "GET /metrics (Prometheus exposition incl. the adopted dispatch "
    "counters), /healthz + /readyz (JSON liveness/readiness with HTTP "
    "200/503 so a plain LB health check works), /flight?kind=&site=&last=N "
    "(flight-recorder tail), /postmortems (list + fetch the "
    "FLAGS_postmortem_dir dumps), /statusz (human-readable runtime state), "
    "and /clockz (the fleet aggregator's clock-offset handshake). -1 "
    "(default) = off; 0 = ephemeral port (tests / chaos fleet workers); "
    "> 0 = fixed port. All read paths are built on detached snapshots, so "
    "a scrape can never block or tear a training step",
)
define_flag(
    "diag_host", "127.0.0.1",
    "bind address of the diagnostics server (FLAGS_diag_port); set to "
    "0.0.0.0 to expose /metrics and the fleet flight-ring pull across "
    "hosts (the FleetAggregator reaches workers at the address they "
    "publish under obs/<job>/<node>)",
)
define_flag(
    "sentinel_pct", 0.0,
    "perf-regression sentinel threshold (paddle.profiler.sentinel): when "
    "> 0, per-(step-signature) step-time EMAs (and serving decode / "
    "queue-wait latencies) are baselined after "
    "FLAGS_sentinel_warmup_steps observations; sustained drift past this "
    "percent (FLAGS_sentinel_sustain_steps consecutive breaches, with "
    "hysteresis — a tripped key re-arms only after drifting back under "
    "half the threshold) emits a 'perf_regression' flight event, "
    "increments perf_regressions, dumps a postmortem whose event tail "
    "shows what changed, and flips /healthz to 503 'degraded'. Breaches "
    "are suppressed while the degradation ladder is demoted or a "
    "checkpoint persist / background compile is in flight (those are "
    "legitimate slowdowns, not regressions). 0 = off",
)
define_flag(
    "sentinel_warmup_steps", 10,
    "observations of a (step-signature) key before the perf-regression "
    "sentinel freezes its baseline EMA and starts drift detection",
)
define_flag(
    "sentinel_sustain_steps", 3,
    "consecutive over-threshold observations before the perf-regression "
    "sentinel trips (and, symmetrically, consecutive recovered "
    "observations before a tripped key clears and re-baselines) — "
    "one-step blips never page",
)
# ---------------------------------------------------------------------------
# Elastic rescale (distributed.fleet.elastic RescaleCoordinator — see
# RESILIENCE.md "Elastic rescale")
# ---------------------------------------------------------------------------
define_flag(
    "elastic_barrier_timeout_s", 20.0,
    "deadline for the membership-epoch barrier (RescaleCoordinator): on a "
    "lease expiry or a new node's register, survivors propose a bumped "
    "epoch and barrier on it; a barrier that cannot complete within this "
    "many seconds (partitioned master, peers wedged) raises "
    "RescaleFallback so the caller escalates to the whole-pod restart "
    "path instead of hanging",
)
define_flag(
    "elastic_rescale_debounce", 2,
    "consecutive membership polls that must observe the SAME changed "
    "member set before a survivor proposes an epoch bump — one flapping "
    "heartbeat (a lease expiring a poll before its refresh lands) must "
    "not tear the fleet through a barrier",
)
define_flag(
    "elastic_straggler_pct", 0.0,
    "fleet straggler threshold: when > 0, each worker compares its own "
    "published step time against the fleet median (per-worker "
    "step-progress heartbeats ride the obs/<job>/<node> KV leases); a "
    "worker sustained past this percent slower than the median for "
    "FLAGS_elastic_straggler_sustain consecutive checks trips a "
    "sentinel-style 'straggler' event, degrades its /healthz, and — with "
    "FLAGS_elastic_straggler_evict — evicts itself through the elastic "
    "shrink path. 0 = off",
)
define_flag(
    "elastic_straggler_sustain", 5,
    "consecutive over-threshold straggler checks before the detector "
    "trips — one GC pause or checkpoint stall never evicts a worker",
)
define_flag(
    "elastic_straggler_evict", False,
    "when the straggler detector trips on THIS worker, deregister its "
    "elastic lease and stop training so survivors rescale in place "
    "(the same shrink path a SIGKILL takes); off = detect and degrade "
    "/healthz only",
)
# ---------------------------------------------------------------------------
# Serving runtime (paddle.serving — see SERVING.md)
# ---------------------------------------------------------------------------
define_flag(
    "serving_block_size", 16,
    "tokens per KV-cache block in the paddle.serving paged cache: every "
    "sequence's context is stored as a chain of fixed-size blocks drawn "
    "from one shared pool, so HBM is bounded by the pool — not by "
    "max_seq_len times the number of admitted sequences",
)
define_flag(
    "serving_num_blocks", 0,
    "KV block-pool size of the paddle.serving engine (shared logical "
    "blocks, each spanning all layers). 0 = derive from the memory budget: "
    "the PR-4 planner traces the decode program, subtracts its non-pool "
    "peak from FLAGS_memory_budget_mb (or detected device HBM), and "
    "floor-divides by the per-block bytes; when no budget is configured "
    "either, a 256-block default applies",
)
define_flag(
    "serving_prompt_buckets", "32,64,128",
    "ascending prompt-length pad boundaries for the serving prefill "
    "programs (io/bucketing.py BucketSpec policy): each admitted prompt is "
    "padded up to its bucket so the number of compiled prefill programs is "
    "bounded; lengths beyond the table round up to multiples of the "
    "largest boundary. Every boundary must divide evenly into "
    "FLAGS_serving_block_size blocks",
)
define_flag(
    "serving_decode_batch_buckets", "1,2,4,8",
    "ascending decode batch-size buckets for continuous batching: each "
    "decode step pads its active-sequence batch up to a bucket (idle rows "
    "attend a per-slot scratch block), so one captured decode program per "
    "(batch bucket, context bucket) signature serves steady state",
)
define_flag(
    "serving_capture", True,
    "capture each serving prefill/decode signature as ONE XLA program "
    "(decode-mode capture, core/lazy.py) and replay it from an LRU cache; "
    "off = every serve step runs per-op eager",
)
define_flag(
    "serving_capture_donate", True,
    "donate the paged KV block-pool buffers to the captured decode "
    "program so each step updates the pool in place (no second pool in "
    "HBM); 0 keeps 1-program capture without donation for code that holds "
    "pool aliases across steps",
)
define_flag(
    "serving_capture_cache_size", 16,
    "LRU cap on captured serving programs (prefill + decode signatures; "
    "0 = unbounded); evictions are counted in "
    "paddle.profiler.dispatch_counters()['serve_capture_evictions']",
)
define_flag(
    "serving_max_new_tokens", 128,
    "default generation cap per serving request when the request does not "
    "set max_new_tokens",
)
define_flag(
    "serving_request_retries", 2,
    "times the serving engine re-enqueues a request whose sequence was "
    "torn down by a non-recoverable (non-injected) fault mid-decode "
    "before answering it with an error response; greedy decode is "
    "deterministic, so a re-run reproduces the same tokens",
)
define_flag(
    "serving_default_deadline_ms", 0.0,
    "default per-request deadline for the paddle.serving engine, in ms "
    "from submit: requests that do not set deadline_ms inherit this. The "
    "deadline is enforced at admission (predicted misses are shed with a "
    "retriable 'overloaded' response), in queue (expired requests answer "
    "'timeout' before wasting a prefill), and mid-decode (expired "
    "sequences leave the batch with a partial 'timeout' response, per "
    "FLAGS_serving_deadline_partial). 0 = no default deadline",
)
define_flag(
    "serving_deadline_partial", True,
    "what a sequence that passes its deadline MID-DECODE answers: on (the "
    "default), a 'timeout' response carrying the tokens generated so far "
    "(partial output is usable under greedy decode); off, the 'timeout' "
    "response carries no tokens. Either way the request gets a terminal "
    "response and its KV blocks are recycled — never a hang or a drop",
)
define_flag(
    "serving_queue_max", 256,
    "cap on the serving RequestQueue (queued, not-yet-admitted requests): "
    "a submit past the cap is shed immediately with a structured, "
    "retriable 'overloaded' response instead of growing host memory "
    "without bound. 0 = unbounded (the pre-overload-control behavior)",
)
define_flag(
    "serving_queue_wait_p99_ms", 0.0,
    "queue-wait p99 trip wire for SLO-aware admission: when the streaming "
    "p99 of observed queue wait (serve_queue_wait_ms histogram) exceeds "
    "this many ms, newly arriving batch-priority requests are shed with "
    "'overloaded' until the p99 recovers — batch traffic sheds first so "
    "it cannot starve interactive under a storm. 0 = trip wire off",
)
define_flag(
    "serving_max_engine_restarts", 3,
    "restarts the serving Supervisor may attempt on a wedged or crashed "
    "engine (tick exceptions escaping the resilience ladder, or the "
    "FLAGS_trace_stall_ms watchdog firing mid-tick) before failing "
    "cleanly: past the cap every queued and in-flight request is answered "
    "with an error response and the engine goes 'dead' — zero hangs",
)
# ---------------------------------------------------------------------------
# Fleet serving front door (paddle.serving.FrontDoor — see SERVING.md)
# ---------------------------------------------------------------------------
define_flag(
    "router_reroute_budget", 2,
    "times the serving FrontDoor may re-dispatch one request to a "
    "surviving replica after its assigned replica died, wedged past its "
    "restart budget, or lost its lease mid-decode (greedy decode makes "
    "the re-run bitwise-identical). Reroutes are counted separately "
    "(router_reroutes) and never burn FLAGS_serving_request_retries; past "
    "the budget the request answers a structured error — never a hang",
)
define_flag(
    "router_refresh_s", 1.0,
    "minimum seconds between FrontDoor routing-table refreshes from the "
    "obs-lease plane (queue depth / cost EMAs / health per replica); "
    "in-process replicas are read live every pump and ignore this",
)
define_flag(
    "router_lease_grace_s", 5.0,
    "how long a remote replica may be absent from a SUCCESSFUL lease read "
    "before the FrontDoor declares it lost and requeues its work. A "
    "failed lease read (master partition) never starts this clock — the "
    "router keeps routing on the last-known table "
    "(router_lease_read_failures counts the outage)",
)
define_flag(
    "router_replica_retries", 2,
    "consecutive transport failures (submit/poll connection errors) "
    "before the FrontDoor declares a remote replica dead and fails its "
    "queued + in-flight work over to survivors",
)
define_flag(
    "router_autoscale_p99_ms", 0.0,
    "fleet-merged queue-wait p99 breach threshold for the FrontDoor "
    "autoscaler: sustained past FLAGS_router_autoscale_sustain_s it "
    "proposes a GROW through the RescaleCoordinator serve-scale document. "
    "0 = autoscale proposals off",
)
define_flag(
    "router_autoscale_sustain_s", 5.0,
    "seconds the fleet queue-wait p99 must stay above "
    "FLAGS_router_autoscale_p99_ms before the autoscaler proposes a grow "
    "(debounce: a transient spike must not scale the fleet)",
)
define_flag(
    "router_autoscale_idle_s", 30.0,
    "seconds the whole fleet must sit idle (no queued, in-flight, or "
    "parked work anywhere) before the autoscaler proposes a shrink: the "
    "victim replica is drained gracefully (no new admissions, in-flight "
    "completes) and then closed",
)
define_flag(
    "router_autoscale_cooldown_s", 30.0,
    "minimum seconds between autoscale proposals (grow or shrink) — the "
    "CheckFreq discipline: let the previous action's effect land in the "
    "measured signals before proposing another",
)
define_flag(
    "use_flash_attention",
    True,
    "route scaled_dot_product_attention through the Pallas flash kernel "
    "when shapes/mask allow (fused_attention_op.cu analogue)",
)
define_flag(
    "fraction_of_gpu_memory_to_use", 0.92,
    "share of the device's reported memory limit a planner may budget when "
    "no explicit budget is given (analysis.memory.plan_block_pool sizes the "
    "serving KV pool against it); the rest stays free for other programs",
)
