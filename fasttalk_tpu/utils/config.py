"""Environment-driven configuration with first-class TPU device selection.

Parity surface: mirrors the reference config fields and env-var names
(reference: app/utils/config.py:63-158) so existing ``.env`` files keep
working, and adds the ``tpu`` branch the reference lacked
(reference: app/utils/config.py:17-60 only knew cuda|cpu|mps) plus the
engine-tuning knobs that used to live in the external vLLM container's
flags (reference: docker-compose.vllm.yml:38-53, .env.vllm.example:32-47).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, fields
from typing import Any

VALID_DEVICES = ("tpu", "cuda", "cpu", "mps")
VALID_PROVIDERS = ("tpu", "vllm", "ollama", "openai", "fake")


def _env_str(name: str, default: str) -> str:
    return os.getenv(name, default)


def _env_int(name: str, default: int) -> int:
    raw = os.getenv(name)
    if raw is None:
        return default
    try:
        return int(raw)
    except ValueError:
        raise ValueError(f"env {name} must be an integer, got {raw!r}") from None


def _env_float(name: str, default: float) -> float:
    raw = os.getenv(name)
    if raw is None:
        return default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(f"env {name} must be a number, got {raw!r}") from None


def _env_bool(name: str, default: bool) -> bool:
    return os.getenv(name, "true" if default else "false").strip().lower() in (
        "1", "true", "yes", "on",
    )


class ComputeDeviceError(RuntimeError):
    """COMPUTE_DEVICE names a device this process cannot use (or is not
    a device name at all). Raised at engine build, never downgraded to
    another device: a server asked for a TPU must not come up on the
    CPU and look healthy."""


def detect_compute_device(requested: str | None = None) -> str:
    """Resolve the compute device by probing the backends.

    ``requested`` defaults to the ``COMPUTE_DEVICE`` env. An explicit
    device must be available to THIS process or ComputeDeviceError is
    raised with the backend's own exception attached ("TPU already in
    use by another process" included). ``auto`` / unset picks the best
    available: tpu → cuda → mps → cpu.

    Probing initialises the JAX backend, which on a TPU host claims the
    chip for this process. Only the process that is about to run the
    engine calls this (engine/factory.py); ``Config()`` never does, so
    an orchestrating parent or ``main.py config --show`` beside a
    running server stays off the device.
    """
    if requested is None:
        requested = os.getenv("COMPUTE_DEVICE", "")
    requested = requested.strip().lower() or "auto"
    if requested not in VALID_DEVICES + ("auto",):
        raise ComputeDeviceError(
            f"COMPUTE_DEVICE={requested!r} is not one of "
            f"{VALID_DEVICES + ('auto',)}")
    if requested == "cpu":
        return "cpu"
    available = {"cpu"}
    try:  # TPU (and JAX GPU) via JAX — the first-class path.
        import jax

        platforms = {d.platform.lower() for d in jax.devices()}
    except ImportError:
        platforms = set()
    except Exception as e:  # backend present but unusable: never "cpu"
        raise ComputeDeviceError(
            f"COMPUTE_DEVICE={requested}: the JAX backend failed to "
            f"initialise ({type(e).__name__}: {e}). A chip held by "
            "another process fails this way; this process will not "
            "fall back to another device.") from e
    if "tpu" in platforms:
        available.add("tpu")
    if platforms & {"gpu", "cuda"}:
        available.add("cuda")
    try:  # torch backends, for reference back-compat (cuda/mps boxes)
        import torch

        if torch.cuda.is_available():
            available.add("cuda")
        if getattr(torch.backends, "mps", None) \
                and torch.backends.mps.is_available():
            available.add("mps")
    except ImportError:
        pass
    if requested != "auto":
        if requested in available:
            return requested
        raise ComputeDeviceError(
            f"COMPUTE_DEVICE={requested}: no {requested} device is "
            f"available to this process, which can reach only "
            f"{sorted(available)} (JAX_PLATFORMS="
            f"{os.getenv('JAX_PLATFORMS', '')!r}); refusing to serve "
            f"on another device")
    return next(d for d in ("tpu", "cuda", "mps", "cpu")
                if d in available)


@dataclass
class Config:
    """All service settings, each overridable via environment variable.

    Reference parity: field/env names follow app/utils/config.py:63-158;
    new TPU-engine fields are grouped at the bottom.
    """

    # The REQUESTED compute device: "auto" or one of VALID_DEVICES.
    # Resolved against the hardware by detect_compute_device() when the
    # engine is built — constructing a Config touches no backend.
    compute_device: str = field(
        default_factory=lambda: _env_str("COMPUTE_DEVICE", "auto")
        .strip().lower() or "auto")

    # Provider: "tpu" (in-tree JAX engine), or legacy "vllm"/"ollama" HTTP
    # passthrough for back-compat (reference: config.py:81).
    llm_provider: str = field(default_factory=lambda: _env_str("LLM_PROVIDER", "tpu"))

    # Model
    model_name: str = field(default_factory=lambda: _env_str("LLM_MODEL", "llama3.2:1b"))
    model_path: str = field(default_factory=lambda: _env_str("MODEL_PATH", "/app/models"))
    tokenizer_path: str = field(default_factory=lambda: _env_str("TOKENIZER_PATH", ""))

    # Legacy backend endpoints (reference: config.py:96-120) — retained so
    # the provider=vllm/ollama back-compat handlers keep working.
    vllm_base_url: str = field(
        default_factory=lambda: _env_str("VLLM_BASE_URL", "http://vllm:8000/v1"))
    vllm_model: str = field(
        default_factory=lambda: _env_str(
            "VLLM_MODEL", "hugging-quants/Meta-Llama-3.1-8B-Instruct-AWQ-INT4"))
    vllm_api_key: str = field(default_factory=lambda: _env_str("VLLM_API_KEY", "not-needed"))
    vllm_timeout: float = field(default_factory=lambda: _env_float("VLLM_TIMEOUT", 600.0))
    ollama_base_url: str = field(
        default_factory=lambda: _env_str("OLLAMA_BASE_URL", "http://ollama:11434"))
    ollama_keep_alive: str = field(default_factory=lambda: _env_str("OLLAMA_KEEP_ALIVE", "5m"))
    ollama_timeout: float = field(default_factory=lambda: _env_float("OLLAMA_TIMEOUT", 600.0))

    # Agent / tools (reference: config.py:102-111)
    enable_agent: bool = field(default_factory=lambda: _env_bool("ENABLE_PYDANTIC_AI", True))
    enable_web_search: bool = field(default_factory=lambda: _env_bool("ENABLE_WEB_SEARCH", True))
    enable_tools: bool = field(default_factory=lambda: _env_bool("ENABLE_TOOLS", True))
    web_search_rate_limit: float = field(
        default_factory=lambda: _env_float("DUCKDUCKGO_RATE_LIMIT", 1.0))
    # auto = live DuckDuckGo with offline fallback; duckduckgo; offline
    web_search_backend: str = field(
        default_factory=lambda: _env_str("WEB_SEARCH_BACKEND", "auto"))
    web_search_timeout: float = field(
        default_factory=lambda: _env_float("WEB_SEARCH_TIMEOUT", 10.0))
    system_prompt: str = field(default_factory=lambda: _env_str(
        "SYSTEM_PROMPT",
        "You are a helpful voice assistant. Keep responses concise and conversational."))

    # Generation defaults (reference: config.py:122-128)
    default_temperature: float = field(
        default_factory=lambda: _env_float("DEFAULT_TEMPERATURE", 0.7))
    default_max_tokens: int = field(default_factory=lambda: _env_int("DEFAULT_MAX_TOKENS", 2048))
    default_context_window: int = field(
        default_factory=lambda: _env_int("DEFAULT_CONTEXT_WINDOW", 8192))
    default_top_p: float = field(default_factory=lambda: _env_float("DEFAULT_TOP_P", 0.9))
    default_top_k: int = field(default_factory=lambda: _env_int("DEFAULT_TOP_K", 40))
    # Unset resolves per provider in __post_init__: 1.1 for the in-tree
    # engine and Ollama (the engine-side default the reference silently
    # relied on — its gateway never set a penalty, but the Ollama engine
    # applied ~1.1 to every generation, reference app/core/
    # ollama_handler.py:144-162); 1.0 for vllm (vLLM's own default —
    # and strict OpenAI-compatible backends 400 on the non-standard
    # repetition_penalty param, so it must not be emitted by default).
    default_repeat_penalty: float = field(
        default_factory=lambda: _env_float("DEFAULT_REPEAT_PENALTY", -1.0))
    default_presence_penalty: float = field(
        default_factory=lambda: _env_float("DEFAULT_PRESENCE_PENALTY", 0.0))
    default_frequency_penalty: float = field(
        default_factory=lambda: _env_float("DEFAULT_FREQUENCY_PENALTY", 0.0))

    # Server (reference: config.py:130-136)
    host: str = field(default_factory=lambda: _env_str("LLM_HOST", "0.0.0.0"))
    port: int = field(default_factory=lambda: _env_int("LLM_PORT", 8000))
    max_connections: int = field(default_factory=lambda: _env_int("LLM_MAX_CONNECTIONS", 50))
    log_level: str = field(default_factory=lambda: _env_str("LOG_LEVEL", "INFO"))

    # Monitoring (reference: config.py:138-142)
    monitoring_port: int = field(default_factory=lambda: _env_int("LLM_MONITORING_PORT", 9092))
    monitoring_host: str = field(
        default_factory=lambda: _env_str("LLM_MONITORING_HOST", "0.0.0.0"))

    # Session (reference: config.py:149-152)
    session_timeout: int = field(default_factory=lambda: _env_int("SESSION_TIMEOUT", 3600))
    # Supervised in-process engine restart after a crash (the in-tree
    # analogue of the reference's docker `restart: unless-stopped`).
    engine_auto_restart: bool = field(
        default_factory=lambda: _env_bool("ENGINE_AUTO_RESTART", True))
    # Restart-storm guard (serving/launcher.py RestartBudget, docs/
    # RESILIENCE.md): at most max restarts per rolling window, with
    # exponential backoff from backoff_s (capped at 60 s) between
    # attempts. On exhaustion the supervisor stops resurrecting and
    # /health reports dead — a persistently poisoned device state
    # must not crash-loop at full CPU.
    supervisor_max_restarts: int = field(
        default_factory=lambda: _env_int("SUPERVISOR_MAX_RESTARTS", 5))
    supervisor_window_s: float = field(
        default_factory=lambda: _env_float("SUPERVISOR_WINDOW_S",
                                           300.0))
    supervisor_backoff_s: float = field(
        default_factory=lambda: _env_float("SUPERVISOR_BACKOFF_S", 2.0))
    # ---- Fault injection (fasttalk_tpu/resilience/failpoints.py,
    # docs/RESILIENCE.md). FAULT_POINTS is a validated spec of named
    # failpoints to arm, e.g.
    # "engine.decode.dispatch=error;count=1,kv.park.copy=delay_ms:250"
    # — unset (the default) compiles the whole subsystem down to one
    # module-flag check per seam (measured <1% tok/s,
    # BENCH_MODE=chaos). FAULT_HTTP gates the runtime
    # POST /debug/fault endpoint on the monitoring port: OFF by
    # default — never enable it in production. ----
    fault_points: str = field(
        default_factory=lambda: _env_str("FAULT_POINTS", ""))
    fault_http_enabled: bool = field(
        default_factory=lambda: _env_bool("FAULT_HTTP", False))
    max_history_length: int = field(default_factory=lambda: _env_int("MAX_HISTORY_LENGTH", 50))
    log_path: str = field(default_factory=lambda: _env_str("LOG_PATH", "./logs"))

    # ---- TPU engine knobs (replace the external engine's flag surface:
    # VLLM_MAX_NUM_SEQS / VLLM_MAX_NUM_BATCHED_TOKENS / GPU_MEMORY_UTILIZATION
    # at .env.vllm.example:32-47) ----
    decode_slots: int = field(default_factory=lambda: _env_int("TPU_DECODE_SLOTS", 16))
    max_model_len: int = field(default_factory=lambda: _env_int("TPU_MAX_MODEL_LEN", 8192))
    prefill_chunk: int = field(default_factory=lambda: _env_int("TPU_PREFILL_CHUNK", 512))
    dtype: str = field(default_factory=lambda: _env_str("TPU_DTYPE", "bfloat16"))
    tp_size: int = field(default_factory=lambda: _env_int("TPU_TP_SIZE", 1))
    dp_size: int = field(default_factory=lambda: _env_int("TPU_DP_SIZE", 1))
    # Sequence-parallel axis: shards each slot's KV over sp chips.
    # Long fresh prompts prefill through ring attention and decode
    # attends via the sharded flash-decoding combine — per-chip serving
    # memory O(T/sp) (parallel/ring_attention.py).
    sp_size: int = field(default_factory=lambda: _env_int("TPU_SP_SIZE", 1))
    # Multi-host SPMD serving role (parallel/spmd_serving.py):
    # "off" | "leader" (serves the gateway; publishes every device call
    # to followers over TPU_SPMD_ADDR) | "follower" (replays the
    # leader's calls against this host's shards; no gateway). Requires
    # the usual jax.distributed env (TPU_COORDINATOR_ADDR,
    # TPU_NUM_PROCESSES, TPU_PROCESS_ID) for the device cluster itself.
    spmd_role: str = field(
        default_factory=lambda: _env_str("TPU_SPMD_ROLE", "off"))
    spmd_addr: str = field(
        default_factory=lambda: _env_str("TPU_SPMD_ADDR",
                                         "127.0.0.1:8890"))
    spmd_followers: int = field(
        default_factory=lambda: _env_int("TPU_SPMD_FOLLOWERS", 1))
    # SPMD cluster liveness (parallel/spmd_serving.py, docs/
    # RESILIENCE.md): the leader heartbeats followers every interval
    # (0 disables the beacon), and a follower treats a leader silent
    # past the timeout as dead (ConnectionError + exit for a cluster
    # restart) instead of blocking in recv until a collective times
    # out.
    spmd_hb_interval_s: float = field(
        default_factory=lambda: _env_float("SPMD_HB_INTERVAL_S", 2.0))
    spmd_hb_timeout_s: float = field(
        default_factory=lambda: _env_float("SPMD_HB_TIMEOUT_S", 8.0))
    hbm_util: float = field(default_factory=lambda: _env_float("TPU_HBM_UTILIZATION", 0.9))
    # The length-pruning Pallas decode-attention kernel (ops/
    # pallas_attention.py). Rides the scatter decode path and composes
    # with KV_QUANT=int8 (fused in-kernel dequant), KV_LAYOUT=paged
    # (block-walking variant), speculative decoding (multi-token verify
    # blocks) and structured decoding. Off by default: profiled on
    # v5e-1 the original q_len=1 bf16 variant's per-grid-cell cost (8
    # statically unrolled tiny GQA matmuls) made it ~2x SLOWER than the
    # XLA attention over a bucketed view at chat-scale lengths — it was
    # the hidden reason r2's int8 measured equal to bf16. Wins where
    # block-level pruning beats reading the whole bucket (long buckets,
    # short active lengths) and on the int8 tier, where it skips the
    # materialised bf16 dequant buffer; see docs/ROOFLINE.md for the
    # measured decision table per config.
    use_pallas_attention: bool = field(
        default_factory=lambda: _env_bool("TPU_USE_PALLAS_ATTENTION", False))
    # Int8 dequant-fused matmul kernel (single-device decode); gates
    # independently of the attention kernel. Unset resolves in
    # __post_init__ to where the engine can run it (on single-device,
    # off on a mesh); an explicit true on a mesh is a named error.
    use_pallas_int8: bool | None = field(
        default_factory=lambda: _env_bool("TPU_USE_PALLAS_INT8", True)
        if os.getenv("TPU_USE_PALLAS_INT8", "").strip() else None)
    # Tokens decoded per device call (lax.scan inside one jitted step) and
    # number of calls kept in flight. Together these amortise and overlap
    # per-call host/dispatch latency and any per-call KV-cache boundary
    # copy. Cost: cancel granularity coarsens to one call. Whether 32
    # and depth 2 are right for a local chip has not been measured
    # (ROADMAP S4).
    decode_steps_per_call: int = field(
        default_factory=lambda: _env_int("TPU_DECODE_STEPS", 32))
    # At 32 steps/call one call's compute already covers the token-fetch
    # round trip, so depth 2 reaches full throughput while keeping the
    # stale-call tail (which delays the NEXT request's first token on the
    # in-order device queue) as short as possible.
    pipeline_depth: int = field(
        default_factory=lambda: _env_int("TPU_PIPELINE_DEPTH", 2))
    # Cross-session shared-prefix KV: a fresh session whose prompt
    # starts with rows resident in another slot (common system prompt)
    # gets them by device copy instead of re-prefill — cuts TTFT and
    # prefill load at high concurrency (single-device path).
    shared_prefix: bool = field(
        default_factory=lambda: _env_bool("TPU_SHARED_PREFIX", True))
    # Speculative decoding: "off" | "ngram" | "auto". "ngram" is the
    # always-on self-drafting prompt-lookup (draft from the slot's own
    # token history on-device, verify draft+1 positions in one
    # scatter-decode block, accept the longest sampled-equal prefix;
    # exactly distribution-preserving, see engine/engine.py
    # _get_spec_decode_fn) — worthwhile on repetitive/structured text,
    # a measured ~25% regression on incompressible sampled text
    # (docs/SPEC_DECODE.md). "auto" (default) makes that call per
    # decode call from the engine's own measured acceptance EMA vs the
    # break-even (TPU_SPEC_BREAKEVEN, default 1.45 plain-step
    # equivalents per verify block), probing periodically — no knob
    # guessing, bounded downside (~1 probe call in 16). Single-device
    # scatter path only; the mesh path always decodes plain.
    spec_decode: str = field(
        default_factory=lambda: _env_str("TPU_SPEC_DECODE", "auto"))
    # Draft tokens proposed per verify block (block = draft + 1).
    spec_draft_len: int = field(
        default_factory=lambda: _env_int("TPU_SPEC_DRAFT", 7))
    # Auto-mode enable threshold: EMA tokens-per-verify-block above
    # which speculative calls win (a verify block costs ~1.43 plain
    # steps on v5e — docs/SPEC_DECODE.md).
    spec_breakeven: float = field(
        default_factory=lambda: _env_float("TPU_SPEC_BREAKEVEN", 1.45))
    # Token sampling candidate preselection: "fast" (block-max, the
    # approx_max_k algorithm — greedy rows stay exact, measured 2.4x
    # cheaper than the full-vocab sort which was ~54% of a decode step)
    # or "exact" (full-vocab lax.top_k).
    sampling: str = field(
        default_factory=lambda: _env_str("TPU_SAMPLING", "fast"))
    # Weight quantization for serving: "none" | "int8" (per-output-channel
    # symmetric, in-tree replacement for the reference's external AWQ
    # engine config, .env.vllm.example:21) | "int4". Legacy alias of
    # WEIGHT_QUANT below — __post_init__ resolves the two into
    # agreement, and setting both to different tiers is a named startup
    # error.
    quantize: str = field(default_factory=lambda: _env_str("TPU_QUANTIZE", "none"))
    # ---- Int4 weight tier (fasttalk_tpu/quantization/,
    # docs/QUANTIZATION.md) ----
    # Serving weight tier: "" (unset -> resolved from TPU_QUANTIZE) |
    # "off" | "int8" | "int4" (group-wise symmetric 4-bit, nibble-
    # packed; the embedding/lm_head stay per-row int8 — the gather and
    # the streaming head kernel want per-row scales).
    weight_quant: str = field(
        default_factory=lambda: _env_str("WEIGHT_QUANT", ""))
    # Contraction rows sharing one int4 scale. Must be even (the nibble
    # packing pairs adjacent rows, and a scale group must never split a
    # pair); that it divides every matmul contraction dim of the model
    # is validated at engine build (quantization/int4.py
    # validate_group).
    weight_quant_group: int = field(
        default_factory=lambda: _env_int("WEIGHT_QUANT_GROUP", 128))
    # AWQ calibration source for scripts/quantize_checkpoint.py: ""
    # (data-free max-abs), "corpus" (the in-tree tinychat corpus), or a
    # path to a text file with one prompt per line. The serving path
    # never calibrates inline — it picks up the prepared cache the CLI
    # writes.
    weight_quant_calib: str = field(
        default_factory=lambda: _env_str("WEIGHT_QUANT_CALIB", ""))
    # Int4 dequant-fused Pallas matmul (single-device T=1 decode,
    # requires WEIGHT_QUANT=int4; ops/pallas_int8.py int4_matmul). Off
    # by default pending on-device benchmarking against the XLA
    # unpack+dequant path, which is always available.
    use_pallas_int4: bool = field(
        default_factory=lambda: _env_bool("TPU_USE_PALLAS_INT4", False))
    # Persistent XLA compilation cache: "" / "on" = enabled, "off" =
    # disabled. Makes warmup a one-time cost per configuration instead
    # of per process. The directory is JAX_COMPILATION_CACHE_DIR when
    # set, else <checkout>/.xla_cache (utils/compile_cache.py).
    compile_cache: str = field(
        default_factory=lambda: _env_str("TPU_COMPILE_CACHE", ""))
    # ---- Admission control / request scheduling (scheduling/
    # scheduler.py, docs/SCHEDULING.md) ----
    # Bound on requests waiting for a decode slot; the excess is shed
    # immediately with a retry_after hint instead of queueing to
    # time out.
    sched_queue_bound: int = field(
        default_factory=lambda: _env_int("SCHED_QUEUE_BOUND", 256))
    # Default queue TTL: a request still waiting past this is expired
    # with a terminal event before it ever touches the TPU. Clients
    # may override per session/request via the "deadline_s" config key.
    sched_default_deadline_s: float = field(
        default_factory=lambda: _env_float("SCHED_DEADLINE_S", 30.0))
    # Priority class when the client sets none: "interactive" admits
    # before "bulk" (clients override via the "priority" config key).
    sched_default_priority: str = field(
        default_factory=lambda: _env_str("SCHED_DEFAULT_PRIORITY",
                                         "interactive"))
    # Starvation guard: a bulk request whose queue wait exceeds this
    # is promoted ahead of interactive work for one admission.
    sched_bulk_aging_s: float = field(
        default_factory=lambda: _env_float("SCHED_AGING_S", 5.0))
    # Graceful drain: how long server shutdown waits for in-flight and
    # queued requests to finish before cancelling the stragglers.
    sched_drain_timeout_s: float = field(
        default_factory=lambda: _env_float("SCHED_DRAIN_TIMEOUT_S", 30.0))
    # Remote providers (vllm/ollama/openai): cap on concurrent upstream
    # requests, so backpressure/shedding applies on the remote branch
    # too (waiters past the admission deadline shed with retry_after).
    remote_max_inflight: int = field(
        default_factory=lambda: _env_int("REMOTE_MAX_INFLIGHT", 32))
    # Bounded jittered retries for idempotent (pre-first-token) remote
    # upstream failures — connect errors and 5xx before any output.
    # 0 disables (first failure surfaces immediately).
    remote_connect_retries: int = field(
        default_factory=lambda: _env_int("REMOTE_CONNECT_RETRIES", 2))
    # ---- Fleet router (fasttalk_tpu/router/, docs/ROUTER.md) ----
    # Front a fleet of engine replicas behind this server instead of a
    # single engine: session-affinity routing, health probes, failover
    # with mid-stream resume, coordinated drain.
    router_enabled: bool = field(
        default_factory=lambda: _env_bool("ROUTER_ENABLED", False))
    # In-process engine replicas the router builds (each a full engine
    # instance: CPU fleets for test/bench, dp-style multi-engine on
    # real hardware). May be 0 when ROUTER_BACKENDS supplies the fleet.
    fleet_replicas: int = field(
        default_factory=lambda: _env_int("FLEET_REPLICAS", 2))
    # Comma-separated serving roots of remote FastTalk replicas
    # (e.g. "http://replica-1:8000,http://replica-2:8000"): generations
    # go through their /v1 surface via the existing remote.py client;
    # probes read their /health body.
    router_backends: str = field(
        default_factory=lambda: _env_str("ROUTER_BACKENDS", ""))
    # Health/load probe cadence (seconds); 0 disables the probe thread
    # (probes then only run on demand — tests).
    router_probe_interval_s: float = field(
        default_factory=lambda: _env_float("ROUTER_PROBE_INTERVAL_S",
                                           2.0))
    # How long an idle session stays pinned to its replica. Default
    # matches KV_PARK_TTL_S: once the parked KV has expired server-side
    # there is nothing left to be sticky to.
    router_affinity_ttl_s: float = field(
        default_factory=lambda: _env_float("ROUTER_AFFINITY_TTL_S",
                                           600.0))
    # Replica failures one request will route around before giving up.
    router_failover_retries: int = field(
        default_factory=lambda: _env_int("ROUTER_FAILOVER_RETRIES", 2))
    # Resume mid-stream failovers on a survivor (re-prefill from the
    # transcript; client sees a `resumed` event). Off = mid-stream
    # replica death surfaces as a terminal error instead.
    router_resume: bool = field(
        default_factory=lambda: _env_bool("ROUTER_RESUME", True))
    # Consecutive failed probes before a replica is marked dead (a
    # stream failing while the backend is unreachable marks it dead
    # immediately, independent of this).
    router_dead_probes: int = field(
        default_factory=lambda: _env_int("ROUTER_DEAD_PROBES", 2))
    # ---- Fleet session fabric (docs/ROUTER.md "Cross-replica KV
    # migration" / "Elastic replicas") ----
    # Move parked session KV between replicas on drain/failover so the
    # next turn RESTORES on the target instead of re-prefilling the
    # transcript. Off = the pre-fabric behaviour (drain releases the
    # entry, failover re-prefills).
    router_migrate: bool = field(
        default_factory=lambda: _env_bool("ROUTER_MIGRATE", True))
    # Hard bound on one migration transfer (export + wire + import).
    # A hung channel falls back to re-prefill — it must never wedge a
    # drain or a failover.
    router_migrate_timeout_s: float = field(
        default_factory=lambda: _env_float("ROUTER_MIGRATE_TIMEOUT_S",
                                           10.0))
    # Serve the /kv/parked/{session_id} migration endpoints on THIS
    # replica's serving port. Off by default: the port is
    # unauthenticated, and the channel exposes parked transcripts
    # (read), pool writes, and purges. Enable ONLY on replicas whose
    # serving port is reachable solely from the router network —
    # a remote router needs it to migrate KV in and out; in-process
    # fleets hand entries over directly and never need it.
    kv_migrate_http: bool = field(
        default_factory=lambda: _env_bool("KV_MIGRATE_HTTP", False))
    # Co-locate sessions sharing a system prompt on the replica that
    # already serves that prefix (hits the shared-prefix stamp /
    # paged block aliasing) while its load is within one queued
    # request of the best candidate.
    router_prefix_affinity: bool = field(
        default_factory=lambda: _env_bool("ROUTER_PREFIX_AFFINITY",
                                          True))
    # Elastic replica scaling (router/elastic.py). FLEET_SCALE_MAX=0
    # disables the scaler (fixed fleet); > 0 lets the launcher grow
    # the in-process fleet up to this size on queue depth / SLO burn
    # and shrink it back to FLEET_SCALE_MIN via client-invisible
    # drain-then-migrate after sustained idleness.
    fleet_scale_min: int = field(
        default_factory=lambda: _env_int("FLEET_SCALE_MIN", 1))
    fleet_scale_max: int = field(
        default_factory=lambda: _env_int("FLEET_SCALE_MAX", 0))
    # Aggregate queued requests across the fleet that trigger a
    # scale-up (an SLO page-burn triggers one regardless of depth).
    fleet_scale_up_queue: int = field(
        default_factory=lambda: _env_int("FLEET_SCALE_UP_QUEUE", 8))
    # Whole-fleet idle time (no queued, no running work) before one
    # replica is retired.
    fleet_scale_down_idle_s: float = field(
        default_factory=lambda: _env_float("FLEET_SCALE_DOWN_IDLE_S",
                                           120.0))
    # Scaler control-loop cadence.
    fleet_scale_check_s: float = field(
        default_factory=lambda: _env_float("FLEET_SCALE_CHECK_S", 5.0))
    # ---- Disaggregated prefill/decode serving (router/disagg.py,
    # docs/ROUTER.md "Disaggregated prefill/decode") ----
    # Per-replica roles for the in-process fleet, comma-separated,
    # one of prefill|decode|mixed per FLEET_REPLICAS slot (e.g.
    # "prefill,decode,decode"). Empty = every replica is "mixed"
    # (today's behaviour). A prefill-role replica runs long-context
    # chunked prefill with a deep queue and ZERO decode slots — it
    # parks the finished KV and the router hands it to the decode
    # tier over the /kv/parked migration wire.
    fleet_roles: str = field(
        default_factory=lambda: _env_str("FLEET_ROLES", ""))
    # Same, for ROUTER_BACKENDS remote replicas (one role per URL).
    router_backend_roles: str = field(
        default_factory=lambda: _env_str("ROUTER_BACKEND_ROLES", ""))
    # Prompt-length threshold (estimated tokens) above which a new
    # stream takes the prefill-tier handoff path; shorter prompts
    # place decode-local. Only meaningful when the fleet has a
    # prefill-role replica.
    disagg_prefill_min_tokens: int = field(
        default_factory=lambda: _env_int("DISAGG_PREFILL_MIN_TOKENS",
                                         512))
    # ---- Session KV host-offload tier (fasttalk_tpu/kvcache/,
    # docs/KVCACHE.md) ----
    # Host-RAM budget for parked session KV (MB). 0 disables the tier
    # (evictions drop residency and a returning session re-prefills,
    # the pre-offload behaviour); negative is a config error. Values
    # above the machine's detectable RAM log a warning.
    kv_host_budget_mb: float = field(
        default_factory=lambda: _env_float("KV_HOST_BUDGET_MB", 0.0))
    # Parked entries idle past this are dropped (host RAM is a cache,
    # not an archive).
    kv_park_ttl_s: float = field(
        default_factory=lambda: _env_float("KV_PARK_TTL_S", 600.0))
    # Proactively snapshot a pinned-but-idle session after this long
    # (slot stays pinned; the copy makes a later eviction free and the
    # history restorable across engine restart). 0 disables idle parks
    # (eviction-time parks still happen).
    kv_park_idle_s: float = field(
        default_factory=lambda: _env_float("KV_PARK_IDLE_S", 30.0))
    # Matched-prefix floor below which restoring is never worth the
    # copy dispatch (the shared-prefix/delta-prefill paths serve).
    kv_restore_min_tokens: int = field(
        default_factory=lambda: _env_int("KV_RESTORE_MIN_TOKENS", 32))
    # ---- Quantized KV-cache tier (ops/kv_quant.py, docs/KVCACHE.md
    # "Quantized tier") ----
    # "none" | "int8": store the KV cache as int8 rows + per-row
    # float32 scales — ~2x resident sessions/context per HBM budget,
    # ~2x effective attention-read bandwidth, and half the bytes
    # through every park/restore/prefix copy. Explicit compatibility
    # matrix (validated below, mirrored in the engine): single-device
    # only (no tp/dp/sp mesh — the scale arrays do not shard with the
    # kv axis yet), XLA attention only (no TPU_USE_PALLAS_ATTENTION —
    # the kernel streams raw rows), and no speculative decoding (the
    # verify block's quantize-on-write is unvalidated; set
    # TPU_SPEC_DECODE=off).
    kv_quant: str = field(
        default_factory=lambda: _env_str("KV_QUANT", "none"))
    # Scale granularity: "token" (one f32 scale per (layer, slot,
    # position) row — the KIVI per-token baseline, cheapest) or
    # "head" (one per kv head per row — tighter when head magnitudes
    # diverge, at num_kv_heads x the scale storage).
    kv_quant_granule: str = field(
        default_factory=lambda: _env_str("KV_QUANT_GRANULE", "token"))
    # ---- Paged KV-cache tier (kvcache/blocks.py, docs/KVCACHE.md
    # "Paged tier") ----
    # "dense" (default) | "paged": the dense layout preallocates
    # [layers, slots, max_len, ...] — every slot priced at worst-case
    # context; the paged layout holds one flat block pool with
    # per-slot block tables, so HBM admission capacity is priced at
    # blocks actually in use and shared prefixes alias (refcount
    # bump) instead of copying rows. Single-device only (the pool and
    # tables are host-orchestrated per chip); composes with KV_QUANT,
    # the host park/offload tier, speculative + structured decoding,
    # and the Pallas decode kernel (block-walking variant).
    kv_layout: str = field(
        default_factory=lambda: _env_str("KV_LAYOUT", "dense"))
    # Tokens per block: power of two in [8, 512]. Small blocks waste
    # less tail capacity per sequence but grow the table/gather work;
    # 16 matches vLLM's default granularity.
    kv_block_size: int = field(
        default_factory=lambda: _env_int("KV_BLOCK_SIZE", 16))
    # Device pool size in blocks. 0 (default) sizes the pool to the
    # dense-equivalent HBM footprint (slots x max_len / block_size);
    # the factory lowers that to what the HBM budget actually holds,
    # which is where the paged layout admits fleets the dense layout
    # rejects.
    kv_pool_blocks: int = field(
        default_factory=lambda: _env_int("KV_POOL_BLOCKS", 0))
    # Decode-growth reserve the admission check must see free beyond
    # the prompt's blocks: "fixed" covers the next KV_RESERVE_TOKENS
    # of growth (default), "max_tokens" the request's whole token
    # budget (no mid-decode sheds, fewest admissions), "none" admits
    # on prefill fit alone (maximum packing, relies on the rehearsed
    # mid-decode shed when the pool runs dry).
    kv_reserve_policy: str = field(
        default_factory=lambda: _env_str("KV_RESERVE_POLICY", "fixed"))
    kv_reserve_tokens: int = field(
        default_factory=lambda: _env_int("KV_RESERVE_TOKENS", 128))
    # ---- Radix automatic prefix cache (kvcache/radix.py,
    # docs/KVCACHE.md "Automatic prefix cache") ----
    # Retired/parked sessions donate their clean prefix blocks to a
    # radix tree keyed by chained block hashes; every admission
    # silently aliases the longest cached chain and prefills only the
    # delta — zero explicit registration. Requires KV_LAYOUT=paged
    # (the tree holds device pool blocks; validated below with a
    # named error). Cached blocks are reclaimed LRU-first under pool
    # pressure before any live admission is shed.
    kv_radix_enabled: bool = field(
        default_factory=lambda: _env_bool("KV_RADIX_ENABLED", False))
    # Free-block headroom the cache must leave after an insert: the
    # tree evicts itself down to this floor so cached prefixes never
    # crowd out the next admission. 0 = rely on pressure eviction
    # alone. Must be < KV_POOL_BLOCKS when that is set.
    kv_radix_min_blocks: int = field(
        default_factory=lambda: _env_int("KV_RADIX_MIN_BLOCKS", 0))
    # "lru" (default): evict least-recently-matched leaves first;
    # "fifo": oldest-inserted first (cheap scans, agent workloads
    # where recency ≈ insertion order anyway).
    kv_radix_evict_policy: str = field(
        default_factory=lambda: _env_str("KV_RADIX_EVICT_POLICY",
                                         "lru"))
    # ---- Structured decoding (fasttalk_tpu/structured/,
    # docs/STRUCTURED.md) ----
    # "auto" (default): constrained requests are served whenever the
    # engine build supports them and rejected with a named reason
    # otherwise; "on": an unsupported build is a CONFIG ERROR at
    # startup (the KV-quant precedent — explicit compat matrix, no
    # silent degrade): single-device only (no tp/dp/sp mesh, no
    # multi-host SPMD) and no Pallas decode attention; "off": the
    # subsystem is disabled and every structured request 400s.
    # Speculative decoding needs no exclusion — it pauses per decode
    # call while a constrained slot is running and resumes after.
    structured_mode: str = field(
        default_factory=lambda: _env_str("STRUCTURED_MODE", "auto"))
    # Per-FSM compile bound: a schema whose token FSM exceeds this
    # many states is rejected with a 400 naming the count.
    structured_max_states: int = field(
        default_factory=lambda: _env_int("STRUCTURED_MAX_STATES", 8192))
    # Device union-arena budget: total FSM states resident across all
    # concurrently served schemas (tables bucket to powers of two
    # below this).
    structured_state_budget: int = field(
        default_factory=lambda: _env_int("STRUCTURED_STATE_BUDGET",
                                         16384))
    # Jump-forward engages when the FSM's forced single-transition
    # chain is at least this many tokens (0 disables jump-forward;
    # decode steps then emit forced tokens one model step each).
    structured_jf_min: int = field(
        default_factory=lambda: _env_int("STRUCTURED_JF_MIN", 4))
    # Compiled-FSM LRU entries per engine (keyed on the canonical
    # schema text; one entry per distinct schema/tokenizer pair).
    structured_cache: int = field(
        default_factory=lambda: _env_int("STRUCTURED_CACHE", 64))
    # response_format={"type":"json_object"} nesting depth: "any JSON"
    # is not regular, so the generic grammar unrolls to this many
    # container levels (scalars only at the innermost).
    structured_json_depth: int = field(
        default_factory=lambda: _env_int("STRUCTURED_JSON_DEPTH", 3))
    # ---- SLOs + stall watchdog (observability/slo.py, watchdog.py,
    # docs/OBSERVABILITY.md). The observability singletons read the
    # same env knobs at construction; the fields here give operators
    # one validated, discoverable surface (to_dict / docs). ----
    # Latency promises for the interactive class (ms); bulk relaxes
    # the latency targets by SLO_BULK_FACTOR (default 4x) unless
    # overridden per class (SLO_BULK_TTFT_P95_MS, ...).
    slo_ttft_p95_ms: float = field(
        default_factory=lambda: _env_float("SLO_TTFT_P95_MS", 1500.0))
    slo_inter_token_p99_ms: float = field(
        default_factory=lambda: _env_float("SLO_INTER_TOKEN_P99_MS",
                                           250.0))
    slo_queue_wait_p95_ms: float = field(
        default_factory=lambda: _env_float("SLO_QUEUE_WAIT_P95_MS",
                                           1000.0))
    slo_error_rate: float = field(
        default_factory=lambda: _env_float("SLO_ERROR_RATE", 0.01))
    # Burn-rate alert thresholds: page on fast+mid windows burning at
    # >= page_burn, warn on mid+long windows at >= warn_burn.
    slo_page_burn: float = field(
        default_factory=lambda: _env_float("SLO_PAGE_BURN", 10.0))
    slo_warn_burn: float = field(
        default_factory=lambda: _env_float("SLO_WARN_BURN", 2.0))
    # While the interactive class page-burns, shed incoming bulk at
    # admission (scheduling/scheduler.py slo_gate).
    slo_shed_bulk_on_page: bool = field(
        default_factory=lambda: _env_bool("SLO_SHED_BULK_ON_PAGE", True))
    # Watchdog: a request with no token for token_stall_s is flagged;
    # past WATCHDOG_CANCEL_STALL_S (default 2x) it is terminated with a
    # terminal error frame. An engine loop heartbeat older than
    # step_stall_s with pending work is a hung step.
    watchdog_token_stall_s: float = field(
        default_factory=lambda: _env_float("WATCHDOG_TOKEN_STALL_S",
                                           30.0))
    watchdog_step_stall_s: float = field(
        default_factory=lambda: _env_float("WATCHDOG_STEP_STALL_S",
                                           15.0))
    # Unset (-1) resolves to 2x the token stall in __post_init__,
    # matching the watchdog's own env fallback.
    watchdog_cancel_stall_s: float = field(
        default_factory=lambda: _env_float("WATCHDOG_CANCEL_STALL_S",
                                           -1.0))
    watchdog_interval_s: float = field(
        default_factory=lambda: _env_float("WATCHDOG_INTERVAL_S", 1.0))
    watchdog_loop_lag_ms: float = field(
        default_factory=lambda: _env_float("WATCHDOG_LOOP_LAG_MS",
                                           500.0))
    # Percentile-window horizon for /stats histograms (seconds): p95s
    # reflect the last metrics_window_s, not hours-old requests
    # (utils/metrics.py). <= 0 restores the pure sample-count window.
    metrics_window_s: float = field(
        default_factory=lambda: _env_float("METRICS_WINDOW_S", 300.0))
    # ---- Performance attribution ledger (observability/perf.py,
    # GET /perf + perf_* gauges) ----
    # Rolling window the attribution report covers (seconds).
    perf_window_s: float = field(
        default_factory=lambda: _env_float("PERF_WINDOW_S", 60.0))
    # Gap between device calls longer than this counts as idle (no
    # work); shorter gaps are host overhead between dispatches.
    perf_idle_gap_ms: float = field(
        default_factory=lambda: _env_float("PERF_IDLE_GAP_MS", 250.0))
    # Roofline peak for MFU (total bf16 TFLOP/s across local devices).
    # 0 = detect from the device kind; unknown kinds report mfu: null.
    perf_peak_tflops: float = field(
        default_factory=lambda: _env_float("PERF_PEAK_TFLOPS", 0.0))
    # Roofline peak for the KV-bandwidth-utilisation figure (total
    # HBM GB/s across local devices). 0 = detect from the device kind;
    # unknown kinds report kv bw_util: null.
    perf_peak_hbm_gbps: float = field(
        default_factory=lambda: _env_float("PERF_PEAK_HBM_GBPS", 0.0))
    # ---- Continuous host profiler (observability/profiler.py,
    # GET /debug/profile + host_gap_causes on /perf) ----
    # Master switch: off spawns no sampler thread and hot paths never
    # touch the profiler (pull-based), so off truly costs nothing.
    prof_enabled: bool = field(
        default_factory=lambda: _env_bool("PROF_ENABLED", True))
    # Sampling rate of the host stack sampler (Hz). 67 deliberately
    # avoids beating against 10/100 Hz periodic work.
    prof_hz: float = field(
        default_factory=lambda: _env_float("PROF_HZ", 67.0))
    # Bound on distinct collapsed stacks kept per thread role; further
    # novel stacks are counted as dropped, not stored.
    prof_max_stacks: int = field(
        default_factory=lambda: _env_int("PROF_MAX_STACKS", 2000))
    # ---- Incident flight recorder (observability/flight.py,
    # POST /debug/bundle) ----
    flight_enabled: bool = field(
        default_factory=lambda: _env_bool("FLIGHT_ENABLED", True))
    flight_dir: str = field(
        default_factory=lambda: _env_str("FLIGHT_DIR",
                                         "/tmp/fasttalk-tpu-flight"))
    # Retention: only the newest N bundle directories are kept.
    flight_max_bundles: int = field(
        default_factory=lambda: _env_int("FLIGHT_MAX_BUNDLES", 8))
    # Rate limit: at most one automatic bundle per this many seconds
    # (a page storm produces one bundle, not a disk-filling flood).
    flight_min_interval_s: float = field(
        default_factory=lambda: _env_float("FLIGHT_MIN_INTERVAL_S",
                                           120.0))
    # > 0: each bundle additionally captures a timed jax.profiler
    # device trace of the next N seconds (off the event loop).
    flight_autoprof_s: float = field(
        default_factory=lambda: _env_float("FLIGHT_AUTOPROF_S", 0.0))
    # This many serving-time recompile events within
    # flight_recompile_window_s counts as a shape-churn incident and
    # triggers a bundle.
    flight_recompile_burst: int = field(
        default_factory=lambda: _env_int("FLIGHT_RECOMPILE_BURST", 5))
    flight_recompile_window_s: float = field(
        default_factory=lambda: _env_float("FLIGHT_RECOMPILE_WINDOW_S",
                                           60.0))
    # How many newest-first events each bundle's events.json carries.
    flight_events_tail: int = field(
        default_factory=lambda: _env_int("FLIGHT_EVENTS_TAIL", 256))
    # ---- Fleet tracing + token journey (docs/OBSERVABILITY.md
    # "Fleet tracing and the token journey") ----
    # Thread the trace id across hops: traceparent headers on router →
    # replica dispatch and /kv/parked migration, adopted by the /v1
    # edge. Off = every process minds its own traces (stitching still
    # works per-process, cross-replica timelines don't).
    trace_propagate: bool = field(
        default_factory=lambda: _env_bool("TRACE_PROPAGATE", True))
    # Server-side kill switch for per-token journey attribution; the
    # per-session journey:true opt-in is ignored when false.
    journey_enabled: bool = field(
        default_factory=lambda: _env_bool("JOURNEY_ENABLED", True))
    # Reconciliation tolerance for derived checks (trace_report.py
    # --journey): |1 - hop_sum/wall| must stay within this fraction.
    journey_tol: float = field(
        default_factory=lambda: _env_float("JOURNEY_TOL", 0.10))
    # ---- Fleet flight recorder (observability/fleetflight.py):
    # router-side incident triggers fan bundle collection out to every
    # live replica (router-fronted processes only) ----
    fleet_flight_enabled: bool = field(
        default_factory=lambda: _env_bool("FLEET_FLIGHT_ENABLED", True))
    fleet_flight_dir: str = field(
        default_factory=lambda: _env_str(
            "FLEET_FLIGHT_DIR", "/tmp/fasttalk-tpu-fleet-flight"))
    fleet_flight_max_bundles: int = field(
        default_factory=lambda: _env_int("FLEET_FLIGHT_MAX_BUNDLES", 4))
    fleet_flight_min_interval_s: float = field(
        default_factory=lambda: _env_float("FLEET_FLIGHT_MIN_INTERVAL_S",
                                           120.0))
    # This many failovers within fleet_flight_window_s counts as a
    # failover burst and triggers a fleet bundle.
    fleet_flight_failover_burst: int = field(
        default_factory=lambda: _env_int("FLEET_FLIGHT_FAILOVER_BURST",
                                         3))
    fleet_flight_window_s: float = field(
        default_factory=lambda: _env_float("FLEET_FLIGHT_WINDOW_S",
                                           60.0))
    # Pre-compile hot shapes at startup: "off" | "fast" | "full" — the
    # in-tree replacement for the reference's 300s engine-container
    # health start_period (docker-compose.vllm.yml:62-67). Empty means
    # provider-dependent: "fast" for the in-tree tpu engine (so the bare
    # `python main.py websocket` never serves first traffic through
    # 20-40s XLA compiles), "off" for remote/fake providers which have
    # nothing to compile.
    warmup: str = field(default_factory=lambda: _env_str("TPU_WARMUP", ""))

    def __post_init__(self) -> None:
        if not self.warmup:
            self.warmup = "fast" if self.llm_provider == "tpu" else "off"
        if self.use_pallas_int8 is None:
            self.use_pallas_int8 = not self._on_mesh()
        if self.watchdog_cancel_stall_s == -1.0:  # unset: 2x token stall
            self.watchdog_cancel_stall_s = 2.0 * self.watchdog_token_stall_s
        if self.default_repeat_penalty < 0:  # unset: provider-resolved
            self.default_repeat_penalty = \
                1.0 if self.llm_provider == "vllm" else 1.1
        # WEIGHT_QUANT unset: resolve it from the legacy TPU_QUANTIZE
        # knob; set: it is authoritative, and the legacy field is
        # brought into agreement (everything downstream may read
        # either). Both set to DIFFERENT tiers is a named error in
        # _validate, not a silent precedence.
        if not self.weight_quant:
            self.weight_quant = {"none": "off"}.get(self.quantize,
                                                    self.quantize)
        elif self.quantize == "none" \
                and self.weight_quant in ("off", "int8", "int4"):
            self.quantize = {"off": "none"}.get(self.weight_quant,
                                                self.weight_quant)
        self._validate()

    def _on_mesh(self) -> bool:
        return self.tp_size > 1 or self.dp_size > 1 or self.sp_size > 1

    def _validate(self) -> None:
        errs: list[str] = []
        # GSPMD cannot partition a custom kernel: the Pallas paths are
        # single-device. A flag the engine cannot honour is a startup
        # error, never a silently dropped flag (mirrored in
        # TPUEngine.__init__).
        if self._on_mesh():
            for flag, env in ((self.use_pallas_attention,
                               "TPU_USE_PALLAS_ATTENTION"),
                              (self.use_pallas_int8,
                               "TPU_USE_PALLAS_INT8"),
                              (self.use_pallas_int4,
                               "TPU_USE_PALLAS_INT4")):
                if flag:
                    errs.append(
                        f"{env}=true is single-device only (the Pallas "
                        f"kernels do not partition over a mesh); set "
                        f"{env}=false or TPU_TP_SIZE=TPU_DP_SIZE="
                        f"TPU_SP_SIZE=1")
        from fasttalk_tpu.utils.compile_cache import OFF_VALUES

        if self.compile_cache.strip().lower() not in (
                "", "on", "1", "true", *OFF_VALUES):
            errs.append(
                f"TPU_COMPILE_CACHE is on|off, got "
                f"{self.compile_cache!r}; place the cache directory "
                f"with JAX_COMPILATION_CACHE_DIR")
        if self.compute_device not in VALID_DEVICES + ("auto",):
            errs.append(f"compute_device must be 'auto' or one of "
                        f"{VALID_DEVICES}")
        if self.llm_provider not in VALID_PROVIDERS:
            errs.append(f"llm_provider must be one of {VALID_PROVIDERS}")
        if not (0.0 <= self.default_temperature <= 2.0):
            errs.append("default_temperature must be in [0, 2]")
        if not (0.0 < self.default_top_p <= 1.0):
            errs.append("default_top_p must be in (0, 1]")
        if self.default_top_k < 0:
            errs.append("default_top_k must be >= 0")
        if self.default_max_tokens <= 0:
            errs.append("default_max_tokens must be > 0")
        if not (0.0 < self.default_repeat_penalty <= 2.0):
            errs.append("default_repeat_penalty must be in (0, 2]")
        if not (-2.0 <= self.default_presence_penalty <= 2.0):
            errs.append("default_presence_penalty must be in [-2, 2]")
        if not (-2.0 <= self.default_frequency_penalty <= 2.0):
            errs.append("default_frequency_penalty must be in [-2, 2]")
        if self.port == self.monitoring_port:
            errs.append("port and monitoring_port must differ")
        if self.max_connections <= 0:
            errs.append("max_connections must be > 0")
        if self.decode_slots <= 0:
            errs.append("decode_slots must be > 0")
        if self.max_model_len <= 0:
            errs.append("max_model_len must be > 0")
        if self.prefill_chunk <= 0 or self.prefill_chunk & (self.prefill_chunk - 1):
            errs.append("prefill_chunk must be a positive power of two")
        if self.tp_size <= 0 or self.dp_size <= 0 or self.sp_size <= 0:
            errs.append("tp_size, dp_size and sp_size must be >= 1")
        if self.spmd_role not in ("off", "leader", "follower"):
            errs.append("spmd_role must be off|leader|follower")
        if self.spmd_role != "off":
            if ":" not in self.spmd_addr:
                errs.append("spmd_addr must be host:port")
            if self.spmd_followers <= 0:
                errs.append("spmd_followers must be >= 1")
        if self.spmd_hb_interval_s < 0:
            errs.append("spmd_hb_interval_s must be >= 0 (0 disables "
                        "the leader heartbeat beacon)")
        if self.spmd_hb_timeout_s < 0:
            errs.append("spmd_hb_timeout_s must be >= 0 (0 disables "
                        "the follower recv deadline)")
        if self.spmd_hb_interval_s > 0 and self.spmd_hb_timeout_s > 0 \
                and self.spmd_hb_timeout_s <= self.spmd_hb_interval_s:
            errs.append(
                "spmd_hb_timeout_s must exceed spmd_hb_interval_s "
                "(a deadline shorter than the beacon period declares "
                "a healthy leader dead)")
        if self.spmd_hb_interval_s == 0 and self.spmd_hb_timeout_s > 0:
            errs.append(
                "SPMD_HB_INTERVAL_S=0 (heartbeats off) requires "
                "SPMD_HB_TIMEOUT_S=0: a follower recv deadline with "
                "no heartbeats on the wire declares a healthy idle "
                "leader dead")
        if self.supervisor_max_restarts < 1:
            errs.append("supervisor_max_restarts must be >= 1")
        if self.supervisor_window_s <= 0:
            errs.append("supervisor_window_s must be > 0")
        if self.supervisor_backoff_s <= 0:
            errs.append("supervisor_backoff_s must be > 0")
        if self.fault_points.strip():
            # Validate the fault-injection spec at startup so a chaos
            # drill with a typo'd point/action is a NAMED config
            # error, never a silently disabled drill
            # (resilience/failpoints.py parse_spec).
            try:
                from fasttalk_tpu.resilience.failpoints import \
                    parse_spec

                parse_spec(self.fault_points)
            except ValueError as e:
                errs.append(str(e))
        if self.decode_steps_per_call <= 0:
            errs.append("decode_steps_per_call must be >= 1")
        if self.spec_decode not in ("off", "ngram", "auto"):
            errs.append(
                f"spec_decode must be off|ngram|auto, "
                f"got {self.spec_decode!r}")
        if self.spec_decode != "off" and not 1 <= self.spec_draft_len <= 31:
            errs.append("spec_draft_len must be in 1..31")
        if self.spec_breakeven <= 0:
            errs.append("spec_breakeven must be > 0")
        if self.pipeline_depth <= 0:
            errs.append("pipeline_depth must be >= 1")
        if self.sampling not in ("fast", "exact"):
            errs.append(f"TPU_SAMPLING must be fast|exact, "
                        f"got {self.sampling!r}")
        if self.quantize not in ("none", "int8", "int4"):
            errs.append("quantize must be 'none', 'int8' or 'int4'")
        # Int4 weight-tier knobs (docs/QUANTIZATION.md): explicit
        # compatibility matrix, mirroring KV_QUANT=int8 below — every
        # unsupported combination is a NAMED startup error, never a
        # silent fall-back.
        if self.weight_quant not in ("off", "int8", "int4"):
            errs.append(f"WEIGHT_QUANT must be off|int8|int4, "
                        f"got {self.weight_quant!r}")
        elif self.quantize in ("none", "int8", "int4") \
                and {"off": "none"}.get(self.weight_quant,
                                        self.weight_quant) != self.quantize:
            errs.append(
                f"WEIGHT_QUANT={self.weight_quant} conflicts with "
                f"legacy TPU_QUANTIZE={self.quantize}; set only "
                f"WEIGHT_QUANT (TPU_QUANTIZE is its alias)")
        if self.weight_quant_group < 2 or self.weight_quant_group % 2:
            errs.append(
                f"WEIGHT_QUANT_GROUP must be an even integer >= 2 (int4 "
                f"packs adjacent rows into one byte, so a scale group "
                f"must never split a nibble pair), got "
                f"{self.weight_quant_group}")
        if self.weight_quant_calib and self.weight_quant_calib != "corpus" \
                and not os.path.isfile(self.weight_quant_calib):
            errs.append(
                f"WEIGHT_QUANT_CALIB must be '' (data-free), 'corpus', "
                f"or a readable prompt file (one per line); no file at "
                f"{self.weight_quant_calib!r}")
        if self.use_pallas_int4 and self.weight_quant != "int4":
            errs.append(
                "TPU_USE_PALLAS_INT4=true requires WEIGHT_QUANT=int4 "
                "(the kernel reads nibble-packed {'q4','s'} leaves)")
        if self.weight_quant == "int4":
            if self.tp_size > 1 or self.dp_size > 1 or self.sp_size > 1:
                errs.append(
                    "WEIGHT_QUANT=int4 is single-device only in v1 "
                    "(partition rules for the q4/scale leaves exist — "
                    "parallel/sharding.py — but the sharded load/init "
                    "path is unvalidated); set TPU_TP_SIZE=TPU_DP_SIZE="
                    "TPU_SP_SIZE=1")
            if self.spmd_role != "off":
                errs.append("WEIGHT_QUANT=int4 is incompatible with "
                            "multi-host SPMD serving; set "
                            "TPU_SPMD_ROLE=off")
        if self.sched_queue_bound <= 0:
            errs.append("sched_queue_bound must be > 0")
        if self.sched_default_deadline_s <= 0:
            errs.append("sched_default_deadline_s must be > 0")
        if self.sched_default_priority not in ("interactive", "bulk"):
            errs.append("sched_default_priority must be "
                        "'interactive' or 'bulk'")
        if self.sched_bulk_aging_s <= 0:
            errs.append("sched_bulk_aging_s must be > 0")
        if self.sched_drain_timeout_s < 0:
            errs.append("sched_drain_timeout_s must be >= 0")
        if self.remote_max_inflight <= 0:
            errs.append("remote_max_inflight must be > 0")
        if self.remote_connect_retries < 0:
            errs.append("remote_connect_retries must be >= 0 "
                        "(0 disables the pre-first-token retry)")
        if self.fleet_replicas < 0:
            errs.append("fleet_replicas must be >= 0")
        if self.router_probe_interval_s < 0:
            errs.append("router_probe_interval_s must be >= 0 "
                        "(0 disables the probe thread)")
        if self.router_affinity_ttl_s <= 0:
            errs.append("router_affinity_ttl_s must be > 0")
        if self.router_failover_retries < 0:
            errs.append("router_failover_retries must be >= 0")
        if self.router_dead_probes < 1:
            errs.append("router_dead_probes must be >= 1")
        if self.router_migrate_timeout_s <= 0:
            errs.append("router_migrate_timeout_s must be > 0 (a hung "
                        "migration must never wedge a drain; disable "
                        "migration with ROUTER_MIGRATE=false instead)")
        if self.fleet_scale_min < 1:
            errs.append("fleet_scale_min must be >= 1 (the fleet "
                        "never scales to zero replicas)")
        if self.fleet_scale_max < 0:
            errs.append("fleet_scale_max must be >= 0 (0 disables "
                        "elastic scaling)")
        if self.fleet_scale_max > 0 \
                and self.fleet_scale_max < self.fleet_scale_min:
            errs.append(f"fleet_scale_max ({self.fleet_scale_max}) "
                        f"must be >= fleet_scale_min "
                        f"({self.fleet_scale_min})")
        if self.fleet_scale_max > 0 and not self.router_enabled:
            errs.append("FLEET_SCALE_MAX > 0 requires "
                        "ROUTER_ENABLED=true (the elastic scaler "
                        "drives a FleetRouter)")
        if self.fleet_scale_up_queue < 1:
            errs.append("fleet_scale_up_queue must be >= 1")
        if self.fleet_scale_down_idle_s <= 0:
            errs.append("fleet_scale_down_idle_s must be > 0")
        if self.fleet_scale_check_s <= 0:
            errs.append("fleet_scale_check_s must be > 0")
        _role_values = ("prefill", "decode", "mixed")
        _all_roles: list[str] = []
        for spec, env, count, what in (
                (self.fleet_roles, "FLEET_ROLES",
                 self.fleet_replicas, "FLEET_REPLICAS"),
                (self.router_backend_roles, "ROUTER_BACKEND_ROLES",
                 len([u for u in self.router_backends.split(",")
                      if u.strip()]), "ROUTER_BACKENDS"),
        ):
            if not spec.strip():
                continue
            roles = [r.strip().lower() for r in spec.split(",")]
            bad = [r for r in roles if r not in _role_values]
            if bad:
                errs.append(f"{env} contains invalid role(s) "
                            f"{bad!r} (each must be one of "
                            f"prefill|decode|mixed)")
                continue
            if len(roles) != count:
                errs.append(f"{env} lists {len(roles)} role(s) but "
                            f"{what} defines {count} replica(s) — "
                            "one role per replica, in order")
                continue
            _all_roles.extend(roles)
        if _all_roles:
            if not self.router_enabled:
                errs.append("FLEET_ROLES/ROUTER_BACKEND_ROLES require "
                            "ROUTER_ENABLED=true (replica roles are a "
                            "router placement concept)")
            if "prefill" in _all_roles and not self.router_migrate:
                errs.append("a 'prefill' replica role requires "
                            "ROUTER_MIGRATE=true (prefill replicas "
                            "hand finished KV to the decode tier over "
                            "the /kv/parked migration wire; without "
                            "migration their output is unreachable)")
            if "prefill" in _all_roles \
                    and not any(r in ("decode", "mixed")
                                for r in _all_roles):
                errs.append("a fleet with 'prefill' roles needs at "
                            "least one 'decode' or 'mixed' replica to "
                            "run the decode side of the handoff")
        if self.disagg_prefill_min_tokens < 1:
            errs.append("disagg_prefill_min_tokens must be >= 1")
        if self.router_enabled:
            n_remote = len([u for u in self.router_backends.split(",")
                            if u.strip()])
            if self.fleet_replicas + n_remote < 1:
                errs.append("router_enabled needs at least one replica "
                            "(FLEET_REPLICAS >= 1 or ROUTER_BACKENDS)")
            if self.spmd_role != "off":
                errs.append("router_enabled is incompatible with "
                            "multi-host SPMD serving (spmd_role must "
                            "be 'off'; an SPMD cluster is ONE logical "
                            "replica — front it via ROUTER_BACKENDS "
                            "from a separate router process)")
        if self.kv_host_budget_mb < 0:
            errs.append("kv_host_budget_mb must be >= 0 (0 disables "
                        "the host-offload tier)")
        if self.kv_park_ttl_s <= 0:
            errs.append("kv_park_ttl_s must be > 0")
        if self.kv_park_idle_s < 0:
            errs.append("kv_park_idle_s must be >= 0 (0 disables "
                        "idle parking)")
        if self.kv_restore_min_tokens < 1:
            errs.append("kv_restore_min_tokens must be >= 1")
        if self.kv_quant not in ("none", "int8"):
            errs.append("kv_quant must be 'none' or 'int8'")
        if self.kv_quant_granule not in ("token", "head"):
            errs.append("kv_quant_granule must be 'token' or 'head'")
        if self.kv_quant == "int8":
            # The quantized tier's compatibility matrix is explicit:
            # every unsupported combination fails HERE with the reason,
            # never silently degrades to bf16 (docs/KVCACHE.md).
            if self.tp_size > 1 or self.dp_size > 1 or self.sp_size > 1:
                errs.append(
                    "KV_QUANT=int8 is single-device only (the per-row "
                    "scale arrays do not shard with the kv axis yet); "
                    "set TPU_TP_SIZE=TPU_DP_SIZE=TPU_SP_SIZE=1")
            if self.spmd_role != "off":
                errs.append("KV_QUANT=int8 is incompatible with "
                            "multi-host SPMD serving (sharded cache); "
                            "set TPU_SPMD_ROLE=off")
            # The Pallas decode-attention kernel composes with this
            # tier: int8 rows + scale arrays DMA into VMEM and
            # dequantize inside the kernel (ops/pallas_attention.py) —
            # no guard needed.
            if self.spec_decode != "off":
                errs.append(
                    "KV_QUANT=int8 is incompatible with speculative "
                    "decoding (the spec carry does not thread the "
                    "scale arrays through the verify block) — set "
                    "TPU_SPEC_DECODE=off")
        if self.kv_layout not in ("dense", "paged"):
            errs.append(f"kv_layout must be 'dense' or 'paged', "
                        f"got {self.kv_layout!r}")
        if (self.kv_block_size < 8 or self.kv_block_size > 512
                or self.kv_block_size & (self.kv_block_size - 1)):
            errs.append(f"kv_block_size must be a power of two in "
                        f"[8, 512], got {self.kv_block_size}")
        if self.kv_pool_blocks < 0:
            errs.append("kv_pool_blocks must be >= 0 (0 sizes the pool "
                        "to the dense-equivalent footprint)")
        if self.kv_reserve_policy not in ("none", "fixed", "max_tokens"):
            errs.append(f"kv_reserve_policy must be none|fixed|"
                        f"max_tokens, got {self.kv_reserve_policy!r}")
        if self.kv_reserve_tokens < 0:
            errs.append("kv_reserve_tokens must be >= 0")
        if self.kv_layout == "paged":
            # Paged compat matrix (docs/KVCACHE.md): named startup
            # errors, never a silent fall-back to dense.
            if self.tp_size > 1 or self.dp_size > 1 or self.sp_size > 1:
                errs.append(
                    "KV_LAYOUT=paged is single-device only (the block "
                    "pool and per-slot tables are host-orchestrated "
                    "per chip); set TPU_TP_SIZE=TPU_DP_SIZE="
                    "TPU_SP_SIZE=1")
            if self.spmd_role != "off":
                errs.append("KV_LAYOUT=paged is incompatible with "
                            "multi-host SPMD serving; set "
                            "TPU_SPMD_ROLE=off")
            if self.kv_block_size > self.max_model_len:
                errs.append(
                    f"kv_block_size ({self.kv_block_size}) must not "
                    f"exceed max_model_len ({self.max_model_len})")
        # Radix prefix-cache compat matrix (docs/KVCACHE.md "Automatic
        # prefix cache"): named startup errors, mirrored in the engine.
        if self.kv_radix_enabled and self.kv_layout != "paged":
            errs.append(
                "KV_RADIX_ENABLED=true requires KV_LAYOUT=paged (the "
                "radix prefix cache holds device pool blocks; the "
                "dense layout has no block pool to cache into)")
        if self.kv_radix_min_blocks < 0:
            errs.append("kv_radix_min_blocks must be >= 0")
        elif self.kv_radix_enabled and self.kv_pool_blocks \
                and self.kv_radix_min_blocks >= self.kv_pool_blocks:
            errs.append(
                f"kv_radix_min_blocks ({self.kv_radix_min_blocks}) "
                f"must be < kv_pool_blocks ({self.kv_pool_blocks}) — "
                "a headroom floor covering the whole pool leaves the "
                "cache nothing to hold")
        if self.kv_radix_evict_policy not in ("lru", "fifo"):
            errs.append(f"kv_radix_evict_policy must be lru|fifo, "
                        f"got {self.kv_radix_evict_policy!r}")
        if self.structured_mode not in ("auto", "on", "off"):
            errs.append(f"structured_mode must be auto|on|off, "
                        f"got {self.structured_mode!r}")
        if self.structured_max_states < 16:
            errs.append(f"structured_max_states must be >= 16, "
                        f"got {self.structured_max_states}")
        if self.structured_state_budget < self.structured_max_states:
            errs.append(
                f"structured_state_budget "
                f"({self.structured_state_budget}) must be >= "
                f"structured_max_states ({self.structured_max_states}) "
                "or the largest admissible FSM could never be pinned")
        if self.structured_jf_min < 0:
            errs.append(f"structured_jf_min must be >= 0 (0 disables "
                        f"jump-forward), got {self.structured_jf_min}")
        if self.structured_cache < 1:
            errs.append(f"structured_cache must be >= 1, "
                        f"got {self.structured_cache}")
        if not 1 <= self.structured_json_depth <= 8:
            errs.append(f"structured_json_depth must be in 1..8, "
                        f"got {self.structured_json_depth}")
        if self.structured_mode == "on":
            # Explicit opt-in makes the compat matrix a startup error
            # with the reason, mirroring KV_QUANT=int8 (docs/
            # STRUCTURED.md): never silently degrade.
            if self.tp_size > 1 or self.dp_size > 1 or self.sp_size > 1:
                errs.append(
                    "STRUCTURED_MODE=on is single-device only in v1 "
                    "(per-slot FSM state is not threaded through the "
                    "sharded decode path); set "
                    "TPU_TP_SIZE=TPU_DP_SIZE=TPU_SP_SIZE=1 or "
                    "STRUCTURED_MODE=auto")
            if self.spmd_role != "off":
                errs.append("STRUCTURED_MODE=on is incompatible with "
                            "multi-host SPMD serving; set "
                            "TPU_SPMD_ROLE=off")
            # The Pallas decode-attention kernel now rides the scatter
            # decode path (pallas_dense/pallas_paged in forward_decode),
            # so constrained decoding composes with it — no guard.
        if self.kv_host_budget_mb > 0:
            # Warn (don't fail) when the budget exceeds detectable host
            # RAM: the pool would page/OOM long before filling.
            try:
                import psutil

                total_mb = psutil.virtual_memory().total / (1024 * 1024)
                if self.kv_host_budget_mb > total_mb:
                    import logging

                    logging.getLogger("fasttalk.config").warning(
                        "KV_HOST_BUDGET_MB=%.0f exceeds detectable "
                        "host RAM (%.0f MB); the pool will hit swap "
                        "or the OOM killer before its budget",
                        self.kv_host_budget_mb, total_mb)
            except Exception:
                pass
        for name in ("slo_ttft_p95_ms", "slo_inter_token_p99_ms",
                     "slo_queue_wait_p95_ms", "slo_page_burn",
                     "slo_warn_burn", "watchdog_token_stall_s",
                     "watchdog_step_stall_s", "watchdog_interval_s",
                     "watchdog_cancel_stall_s", "watchdog_loop_lag_ms"):
            if getattr(self, name) <= 0:
                errs.append(f"{name} must be > 0")
        if not (0.0 < self.slo_error_rate <= 1.0):
            errs.append("slo_error_rate must be in (0, 1]")
        if self.perf_window_s <= 0:
            errs.append("perf_window_s must be > 0")
        if self.perf_idle_gap_ms <= 0:
            errs.append("perf_idle_gap_ms must be > 0")
        if self.perf_peak_tflops < 0:
            errs.append("perf_peak_tflops must be >= 0 (0 = detect "
                        "from the device kind)")
        if self.perf_peak_hbm_gbps < 0:
            errs.append("perf_peak_hbm_gbps must be >= 0 (0 = detect "
                        "from the device kind)")
        if self.prof_hz <= 0 or self.prof_hz > 1000:
            errs.append("prof_hz must be in (0, 1000] — the host "
                        "stack sampler rate in Hz")
        if self.prof_max_stacks < 16:
            errs.append("prof_max_stacks must be >= 16 (the bound on "
                        "distinct stacks kept per thread role)")
        if not self.flight_dir.strip():
            errs.append("flight_dir must be a non-empty path")
        if self.flight_max_bundles < 1:
            errs.append("flight_max_bundles must be >= 1")
        if self.flight_min_interval_s < 0:
            errs.append("flight_min_interval_s must be >= 0")
        if self.flight_autoprof_s < 0:
            errs.append("flight_autoprof_s must be >= 0 (0 disables "
                        "the automatic profiler capture)")
        if self.flight_recompile_burst < 2:
            errs.append("flight_recompile_burst must be >= 2 (one "
                        "recompile is an event, not an incident)")
        if self.flight_recompile_window_s <= 0:
            errs.append("flight_recompile_window_s must be > 0")
        if self.flight_events_tail < 1:
            errs.append("flight_events_tail must be >= 1")
        if not (0 < self.journey_tol < 1):
            errs.append("journey_tol must be in (0, 1) — a fraction "
                        "of wall clock the hop sum may miss by")
        if not self.fleet_flight_dir.strip():
            errs.append("fleet_flight_dir must be a non-empty path")
        if self.fleet_flight_max_bundles < 1:
            errs.append("fleet_flight_max_bundles must be >= 1")
        if self.fleet_flight_min_interval_s < 0:
            errs.append("fleet_flight_min_interval_s must be >= 0")
        if self.fleet_flight_failover_burst < 2:
            errs.append("fleet_flight_failover_burst must be >= 2 "
                        "(one failover is an event, not an incident)")
        if self.fleet_flight_window_s <= 0:
            errs.append("fleet_flight_window_s must be > 0")
        if self.watchdog_cancel_stall_s < self.watchdog_token_stall_s:
            # Cancellation cannot precede detection; a smaller value
            # would silently mean max(token, cancel) (watchdog.py).
            errs.append("watchdog_cancel_stall_s must be >= "
                        "watchdog_token_stall_s")
        if self.warmup not in ("off", "fast", "full"):
            errs.append("warmup must be 'off', 'fast' or 'full'")
        if self.default_context_window < self.default_max_tokens:
            # Reference warns here (config.py:184-187); we keep it a warning.
            pass
        if errs:
            raise ValueError("Invalid configuration: " + "; ".join(errs))

    # Presets mirror reference config.py:270-315 (fast/balanced/quality).
    def apply_preset(self, name: str) -> None:
        presets = {
            "fast": dict(default_temperature=0.5, default_max_tokens=512,
                         default_top_p=0.85, default_top_k=20),
            "balanced": dict(default_temperature=0.7, default_max_tokens=2048,
                             default_top_p=0.9, default_top_k=40),
            "quality": dict(default_temperature=0.9, default_max_tokens=4096,
                            default_top_p=0.95, default_top_k=80),
        }
        if name not in presets:
            raise ValueError(f"Unknown preset {name!r}; choose from {sorted(presets)}")
        for k, v in presets[name].items():
            setattr(self, k, v)
        self._validate()

    def to_dict(self) -> dict[str, Any]:
        return {f.name: getattr(self, f.name) for f in fields(self)}


_config: Config | None = None


def get_config(reload: bool = False) -> Config:
    global _config
    if _config is None or reload:
        _config = Config()
    return _config
