"""The TPU inference engine: continuous batching over fixed decode slots.

This replaces the external vLLM/Ollama containers of the reference with an
in-process JAX engine (SURVEY.md §7 design stance: the engine is an
in-process library behind the same async-generator seam the reference
handlers exposed, vllm_handler.py:216-225).

Architecture (JetStream-style, XLA-first):

- **Fixed shapes.** S decode slots; one jitted decode step advances all
  slots at once. Prefill is chunked into power-of-two buckets; each bucket
  compiles once. KV-length buckets bound attention cost: the decode step
  is compiled per cache-prefix length in {512, 1024, ...} and the engine
  picks the smallest bucket covering the longest active sequence.
- **Donated KV cache.** The cache pytree is donated through every jitted
  call, so K/V updates happen in place in HBM. Idle slots are excluded
  from cache writes by a per-slot write mask, so a parked session's
  resident KV can never be clobbered by the batched step.
- **Single engine thread** owns every device interaction; asyncio callers
  talk to it through a command queue, and token deltas travel back via
  ``loop.call_soon_threadsafe`` onto per-request ``asyncio.Queue``s. A
  generation is therefore fully async on the serving side — the
  event-loop-stalling sync-generator bug of the reference
  (websocket_server_vllm.py:578, SURVEY.md §3.3 warning) cannot occur.
- **Device-resident decode state, multi-token calls, pipelined dispatch.**
  Positions, active mask, per-slot sampling params, the current token and
  the PRNG key all live on the device and are chained call-to-call; one
  jitted call runs ``steps_per_call`` decode steps under ``lax.scan`` and
  returns all sampled tokens, and up to ``pipeline_depth`` calls stay in
  flight so the host-side fetch/detokenise of call N overlaps the device
  compute of call N+1. Host mirrors are reconciled (and re-uploaded) only
  when the slot set changes — request admission, completion, cancel. A
  slot that finishes mid-call keeps decoding garbage until the pipeline
  drains; those tokens are dropped on the host and their (masked or
  past-the-kept-length) KV writes are never attended to.
- **Mid-decode cancellation.** Cancel is a command; the engine deactivates
  the slot at the next step boundary, freeing capacity immediately
  (reference flaw: cancel could not even be received until generation
  completed, SURVEY.md §3.6).
- **KV residency across turns.** Sessions pin slots (engine/slots.py);
  a follow-up turn prefills only the token delta after prefix matching.
- **Shared-prefix KV.** A fresh session whose prompt starts with rows
  resident in ANOTHER slot (common system prompt) gets them by device
  copy — cross-session at admission, and intra-batch for cold bursts
  (leader prefills, members stamp; see _prefill_batched_shared).
- **Speculative decoding** (default "auto"): on-device prompt-lookup
  drafts verified as multi-token scatter-decode blocks, exactly
  distribution-preserving; the dispatcher engages them per call from
  the measured acceptance EMA (see _get_spec_decode_fn,
  _spec_call_wanted and docs/SPEC_DECODE.md).
"""

from __future__ import annotations

import asyncio
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import partial
from typing import Any, AsyncGenerator

import jax
import jax.numpy as jnp
import numpy as np

from fasttalk_tpu.engine.slots import Slot, SlotManager, _lcp
from fasttalk_tpu.engine.tokenizer import StreamDetokenizer, Tokenizer
from fasttalk_tpu.kvcache import (HostKVPool, KVOffloader, RestorePolicy,
                                  entry_problem, kv_env_defaults,
                                  strip_device)
from fasttalk_tpu.kvcache.blocks import BlockAllocator, blocks_for
from fasttalk_tpu.kvcache.radix import RadixTree
from fasttalk_tpu.kvcache.offload import (kv_bucket, make_kv_restore_fn,
                                          make_kv_slice_fn,
                                          make_paged_kv_restore_fn,
                                          make_paged_kv_slice_fn,
                                          pad_rows)
from fasttalk_tpu.models.configs import ModelConfig
from fasttalk_tpu.models.llama import (KVCache, forward, forward_decode,
                                       init_cache, init_paged_cache)
from fasttalk_tpu.observability.events import get_events
from fasttalk_tpu.observability.perf import get_perf, program_key
from fasttalk_tpu.resilience import failpoints as _fp
from fasttalk_tpu.observability.slo import get_slo
from fasttalk_tpu.observability.trace import get_tracer
from fasttalk_tpu.ops.quant import traced_paths
from fasttalk_tpu.ops.sampling import (apply_penalties, penalize_values,
                                       sample_tokens)
from fasttalk_tpu.scheduling.scheduler import RequestScheduler
from fasttalk_tpu.structured.compiler import (FSMCompiler,
                                              StructuredError,
                                              validate_structured_spec)
from fasttalk_tpu.structured.fsm import FSMTooLarge, TokenFSM
from fasttalk_tpu.structured.runtime import (ArenaFull, FSMArena,
                                             pack_mask_row)
from fasttalk_tpu.utils.errors import (ENGINE_SHED_CODES,
                                       AdmissionRejected, ErrorCategory,
                                       LLMServiceError)
from fasttalk_tpu.utils.logger import get_logger
from fasttalk_tpu.utils.metrics import get_metrics

log = get_logger("engine")

_KV_BUCKETS = (512, 1024, 2048, 4096, 8192, 16384, 32768)
_PREFILL_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048)


@dataclass
class GenerationParams:
    temperature: float = 0.7
    top_k: int = 40
    top_p: float = 0.9
    max_tokens: int = 2048
    stop: list[str] = field(default_factory=list)
    # Penalties against the current generation's emitted tokens, applied
    # on device by ops/sampling.apply_penalties. Neutral at the engine
    # seam (1.0 / 0.0 / 0.0); the serving layer defaults repeat_penalty
    # to 1.1 (Config), matching the Ollama engine-side default the
    # reference silently relied on.
    repeat_penalty: float = 1.0
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # vLLM-parity extension (SamplingParams.ignore_eos): decode to the
    # token budget instead of stopping on EOS — fixed-length benching
    # and forced continuation.
    ignore_eos: bool = False
    # Disaggregated prefill tier (router/disagg.py): run ONLY the
    # prompt's chunked prefill, park the finished KV rows to the host
    # pool, and finish with reason "prefill_parked" — no first-token
    # sample, no decode-slot occupancy. The router then migrates the
    # parked entry to a decode replica over /kv/parked. Internal to
    # the router handoff; not client-settable through serving.
    prefill_only: bool = False

    def __post_init__(self) -> None:
        # Client-reachable values: apply_penalties DIVIDES by
        # repeat_penalty, so 0/negative/NaN would poison the whole
        # generation with inf logits rather than erroring. Raising here
        # surfaces as a 400 on /v1 and an invalid_config error frame on
        # the WS (caught before the circuit breaker — a client-shape
        # error must not open the shared breaker, serving/server.py).
        import math

        if not (math.isfinite(self.repeat_penalty)
                and 0.0 < self.repeat_penalty <= 2.0):
            raise ValueError(
                f"repeat_penalty must be in (0, 2], got "
                f"{self.repeat_penalty}")
        if not math.isfinite(self.presence_penalty):
            raise ValueError("presence_penalty must be finite")
        if not math.isfinite(self.frequency_penalty):
            raise ValueError("frequency_penalty must be finite")
        if self.priority not in ("interactive", "bulk"):
            raise ValueError(
                f"priority must be 'interactive' or 'bulk', "
                f"got {self.priority!r}")
        if self.deadline_s is not None:
            try:
                ok = math.isfinite(self.deadline_s) and self.deadline_s > 0
            except TypeError:
                ok = False
            if not ok:
                raise ValueError(
                    f"deadline_s must be a positive number, "
                    f"got {self.deadline_s!r}")
        if self.prefill_only and self.structured is not None:
            raise ValueError(
                "prefill_only is incompatible with structured output "
                "(the FSM samples the first token under its start-state "
                "mask; a prefill-tier request never samples)")
        if self.structured is not None:
            # Shape errors surface here (400 / invalid_config);
            # compile errors surface at the engine seam the same way.
            self.structured = validate_structured_spec(self.structured)
            if self.ignore_eos:
                raise ValueError(
                    "structured output is incompatible with "
                    "ignore_eos=true (the FSM decides where the "
                    "document ends)")
            if self.stop:
                raise ValueError(
                    "structured output is incompatible with stop "
                    "sequences: a stop string could truncate the "
                    "document mid-grammar and break the validity "
                    "guarantee")
    # Text-completion mode (/v1/completions): the prompt is the joined
    # message content, tokenized verbatim (BOS + bytes, no chat
    # template). Out of band on purpose — an in-band role sentinel
    # would let chat clients bypass the template.
    raw_prompt: bool = False
    # Admission-control class and queue TTL (scheduling/scheduler.py):
    # "interactive" admits before "bulk"; deadline_s bounds how long
    # the request may wait in the admission queue before it is expired
    # with a terminal event (None = the scheduler's configured
    # default). Client-settable per session/request.
    priority: str = "interactive"
    deadline_s: float | None = None
    # Constrained decoding (docs/STRUCTURED.md): a structured spec
    # ({"kind": "json_object" | "json_schema" | "regex" | "tool_call",
    # ...}) compiled to a token FSM whose allowed-token mask is applied
    # inside the jitted sampler every step. None = unconstrained (the
    # zero-cost default). Validated here so a malformed spec surfaces
    # as a 400 / invalid_config, never a 500.
    structured: Any = None
    # Per-token journey waterfall (observability/journey.py): when set,
    # the engine stamps each token event with its device-fetch /
    # detok-emit boundaries (the "j" dict) so the serving layer can cut
    # TTFT and inter-token gaps into named hops. Off by default — two
    # time.monotonic() calls per retirement are cheap but not free.
    journey: bool = False


def raw_prompt_text(messages: list[dict]) -> str:
    """The raw completion prompt for ``raw_prompt=True``: joined message
    content. One definition for every backend (tpu/vllm/ollama must
    produce the same prompt for the same request)."""
    return "".join(str(m.get("content") or "") for m in messages)


@dataclass
class _PrefillState:
    """A long prompt being prefilled chunk-by-chunk, interleaved with
    decode calls so running sessions keep streaming (one chunk per engine
    loop iteration; the reference's analogue was head-of-line blocking
    the whole gateway on a single HTTP request)."""

    req: "_Request"
    slot: Slot
    start: int
    todo: list[int]
    t0: float = field(default_factory=time.monotonic)
    last_logits: Any = None


@dataclass
class _Request:
    request_id: str
    session_id: str
    prompt_tokens: list[int]
    params: GenerationParams
    out_queue: asyncio.Queue
    loop: asyncio.AbstractEventLoop
    submitted_at: float = field(default_factory=time.monotonic)
    detok: StreamDetokenizer | None = None
    slot: Slot | None = None
    generated: int = 0
    pending_text: str = ""     # held back for stop-string matching
    emit_buf: str = ""         # text batched within one retirement
    first_token_at: float | None = None
    first_pending: bool = False  # first sampled token not yet fetched
    cancelled: bool = False
    finished: bool = False
    # Observability timestamps/accumulators (observability/trace.py):
    # written only at phase transitions or with O(ns) per-token adds.
    admitted_at: float | None = None    # popped from the waiting queue
    decode_started_at: float | None = None  # activation (prefill done)
    last_token_at: float | None = None  # inter-token gap tracking
    detok_s: float = 0.0                # cumulative detokenize time
    spec_accepted: int = 0              # accepted draft tokens
    spec_drafted: int = 0               # drafts offered to verification
    # Watchdog/SLO stamps (observability/watchdog.py, slo.py):
    last_progress_at: float | None = None  # any forward progress
    max_gap_ms: float = 0.0             # worst inter-token gap seen
    stall_failed: bool = False          # terminated by the watchdog
    slo_recorded: bool = False          # sample already fed to the SLO
    prefill_tokens: int = 0             # tokens actually prefilled
    #   (after resident/restored/shared reuse) — feeds the restore
    #   policy's measured prefill-throughput EMA (kvcache/policy.py)
    # Constrained decoding (docs/STRUCTURED.md): the compiled token
    # FSM, its arena registration, and the HOST-side mirror of the
    # per-slot FSM state (replayed token-by-token at retirement; the
    # authoritative copy advances on device inside the decode scan).
    fsm: TokenFSM | None = None
    fsm_entry: Any = None               # structured/runtime._Entry
    fsm_state: int = 0                  # local (per-FSM) state id
    jump_tokens: int = 0                # tokens emitted by jump-forward


class EngineBase:
    """The engine seam the serving layer depends on. Mirrors the surface
    of the reference's backend handlers (generate stream + connection
    check + model info + cancel, vllm_handler.py:117-326) as one async
    interface; tests substitute a FakeEngine."""

    # Disaggregated-serving replica role (router/disagg.py): "mixed"
    # serves prefill + decode (today's behaviour); "prefill" admits
    # ONLY prefill_only handoff requests (zero decode-slot occupancy);
    # "decode" is a placement hint — the engine itself admits
    # everything. Set by the fleet builder, read by the role gate in
    # TPUEngine.generate.
    role: str = "mixed"

    async def generate(self, request_id: str, session_id: str,
                       messages: list[dict], params: GenerationParams,
                       ) -> AsyncGenerator[dict, None]:
        raise NotImplementedError
        yield  # pragma: no cover

    def cancel(self, request_id: str) -> bool:
        raise NotImplementedError

    def release_session(self, session_id: str) -> None:
        raise NotImplementedError

    def check_connection(self) -> bool:
        raise NotImplementedError

    def get_model_info(self) -> dict:
        raise NotImplementedError

    def get_stats(self) -> dict:
        raise NotImplementedError

    def start(self) -> None:
        raise NotImplementedError

    def shutdown(self) -> None:
        raise NotImplementedError

    def warmup(self, level: str = "off") -> None:
        """Pre-compile hot shapes before serving traffic (no-op by
        default; the TPU engine overrides)."""

    def begin_drain(self) -> None:
        """Graceful-drain mode: reject NEW submissions (with a
        retry_after hint) while in-flight and already-queued requests
        finish. No-op by default; engines with admission control
        override. Wired into server shutdown (serving/server.py)."""

    def pending_requests(self) -> int:
        """Requests still queued or running (drain-progress probe)."""
        return 0

    def set_trace_component(self, component: str) -> None:
        """Tag this engine's spans with a fleet component name (e.g.
        ``inproc-0``) so in-proc replicas sharing one process tracer
        stay distinguishable in stitched traces (observability/
        stitch.py). No-op by default; engines that hold a tracer
        override by rebinding it to ``get_tracer().scoped(name)``."""

    # ---- fleet fabric: cross-replica KV migration (docs/ROUTER.md).
    # Engines without a host pool answer None/False — the router then
    # falls back to re-prefill, which is always safe.

    def export_parked_kv(self, session_id: str):
        """A session's parked host-KV entry (``ParkedKV``), stripped of
        device-staged buffers, or None. Peek only: the source keeps
        owning the entry until the migration confirms and calls
        :meth:`drop_parked_kv`."""
        return None

    def import_parked_kv(self, entry) -> bool:
        """Adopt a migrated entry into this engine's host pool. False
        when the entry is refused (shape/tier mismatch, pool disabled,
        over budget) — the refusal leaves the pool untouched."""
        return False

    def drop_parked_kv(self, session_id: str) -> bool:
        """Purge one session's parked entry (migration source cleanup;
        touches ONLY the host pool, so it is safe on a replica whose
        engine thread is down)."""
        return False

    def parked_kv_info(self, session_id: str) -> tuple[int, int] | None:
        """(kept_tokens, nbytes) of a session's parked entry, or None —
        the cheap metadata the migration policy prices before moving
        any bytes."""
        return None


class TPUEngine(EngineBase):
    """The real engine. Owns params, KV cache, tokenizer, decode loop."""

    def __init__(self, model_cfg: ModelConfig, params: Any,
                 tokenizer: Tokenizer, *, num_slots: int = 16,
                 max_len: int = 8192, prefill_chunk: int = 512,
                 dtype: Any = jnp.bfloat16, seed: int = 0,
                 context_window: int | None = None, mesh: Any = None,
                 use_pallas_attention: bool = False,
                 use_pallas_int8: bool | None = None,
                 weight_quant: str = "off",
                 weight_quant_group: int = 128,
                 use_pallas_int4: bool = False,
                 steps_per_call: int = 8, pipeline_depth: int = 2,
                 sampling_method: str = "fast",
                 spec_decode: str = "off", spec_draft_len: int = 7,
                 spec_breakeven: float = 1.45,
                 shared_prefix: bool = True,
                 queue_bound: int = 256,
                 default_deadline_s: float = 30.0,
                 bulk_aging_s: float = 5.0,
                 kv_host_budget_mb: float | None = None,
                 kv_park_ttl_s: float | None = None,
                 kv_park_idle_s: float | None = None,
                 kv_restore_min_tokens: int | None = None,
                 kv_quant: str = "none",
                 kv_quant_granule: str = "token",
                 kv_layout: str = "dense",
                 kv_block_size: int = 16,
                 kv_pool_blocks: int = 0,
                 kv_reserve_policy: str = "fixed",
                 kv_reserve_tokens: int = 128,
                 kv_radix: bool = False,
                 kv_radix_min_blocks: int = 0,
                 kv_radix_evict_policy: str = "lru",
                 structured: str = "auto",
                 structured_max_states: int = 8192,
                 structured_state_budget: int = 16384,
                 structured_jf_min: int = 4,
                 structured_cache: int = 64,
                 structured_json_depth: int = 3):
        self.cfg = model_cfg
        self.params = params
        self.tokenizer = tokenizer
        self.num_slots = num_slots
        # Cache length rounds up to the bucket granule: the flash prefill
        # (block 512) and the Pallas decode kernel (block 128) both need
        # a divisible key axis, and an off-granule TPU_MAX_MODEL_LEN like
        # 1000 is a legal config. The request-visible limit stays at the
        # configured length via usable_len.
        self.max_len = -(-max_len // _KV_BUCKETS[0]) * _KV_BUCKETS[0]
        self.usable_len = min(max_len, context_window or max_len)
        self.prefill_chunk = min(prefill_chunk, max(_PREFILL_BUCKETS))
        self.dtype = dtype
        self.mesh = mesh
        # GSPMD cannot partition a custom kernel over a mesh; the Pallas
        # paths are single-device optimisations only. A flag that cannot
        # be honoured is a construction error (Config mirrors it), never
        # a dropped flag. use_pallas_int8=None means "where it can run":
        # on single-device, off on a mesh. The kernels gate
        # independently.
        if mesh is not None:
            for flag, env in ((use_pallas_attention,
                               "TPU_USE_PALLAS_ATTENTION"),
                              (use_pallas_int8, "TPU_USE_PALLAS_INT8"),
                              (use_pallas_int4, "TPU_USE_PALLAS_INT4")):
                if flag:
                    raise ValueError(
                        f"{env}=true is single-device only (the Pallas "
                        f"kernels do not partition over a mesh); set "
                        f"{env}=false or drop the mesh")
        self.use_pallas_attention = use_pallas_attention
        self.use_pallas_int8 = (mesh is None if use_pallas_int8 is None
                                else use_pallas_int8)
        # Int4 weight tier (fasttalk_tpu/quantization/, docs/
        # QUANTIZATION.md): the seven layer matmuls carry nibble-packed
        # {"q4", "s"} leaves and dequantize inside the matmul operand
        # read (ops/quant.py). The compat matrix is EXPLICIT, mirroring
        # the Config checks so library callers get the same named
        # errors: int4 COMPOSES with KV_QUANT=int8, KV_LAYOUT=paged,
        # speculative and structured decoding (all downstream of the
        # logits); it rejects a mesh (the sharded load/init path for
        # packed leaves is unvalidated — the partition rules exist in
        # parallel/sharding.py).
        if weight_quant not in ("off", "int8", "int4"):
            raise ValueError(f"weight_quant must be 'off', 'int8' or "
                             f"'int4', got {weight_quant!r}")
        self.weight_quant = weight_quant
        self.weight_quant_group = int(weight_quant_group)
        if weight_quant == "int4":
            from fasttalk_tpu.quantization.int4 import validate_group

            if mesh is not None:
                raise ValueError(
                    "WEIGHT_QUANT=int4 is single-device only in v1: the "
                    "partition rules for {'q4','s'} leaves exist "
                    "(parallel/sharding.py) but the sharded load/init "
                    "path is unvalidated — set TPU_TP_SIZE=TPU_DP_SIZE="
                    "TPU_SP_SIZE=1")
            validate_group(model_cfg, self.weight_quant_group)
        if use_pallas_int4 and weight_quant != "int4":
            raise ValueError(
                "TPU_USE_PALLAS_INT4=true requires WEIGHT_QUANT=int4 "
                "(the kernel reads nibble-packed {'q4','s'} leaves)")
        self.use_pallas_int4 = use_pallas_int4
        # Int8 KV-cache tier (ops/kv_quant.py, docs/KVCACHE.md): the
        # cache stores int8 rows + per-row float32 scales; every KV
        # touchpoint (decode scatter, the prefill paths, prefix copy,
        # host park/restore) moves the quantized domain, halving
        # resident HBM, attention-read bandwidth and offload copy
        # bytes. The compatibility matrix is EXPLICIT — unsupported
        # combinations raise here (and at Config validation with the
        # same reasons) rather than silently degrading:
        # - mesh: the scale arrays do not shard with the kv axis yet;
        # - speculative decoding: the spec carry does not thread the
        #   scale arrays through the verify block.
        # The Pallas decode kernel COMPOSES with this tier: int8 rows
        # + scales DMA into VMEM and dequantize inside the kernel
        # (ops/pallas_attention.py).
        if kv_quant not in ("none", "int8"):
            raise ValueError(f"kv_quant must be 'none' or 'int8', "
                             f"got {kv_quant!r}")
        self.kv_quant = kv_quant == "int8"
        if self.kv_quant:
            from fasttalk_tpu.ops.kv_quant import granule_dim

            if mesh is not None:
                raise ValueError(
                    "KV_QUANT=int8 is single-device only: the per-row "
                    "scale arrays do not shard with the kv axis yet")
            if spec_decode in ("ngram", "auto"):
                raise ValueError(
                    "KV_QUANT=int8 is incompatible with speculative "
                    "decoding (the spec carry does not thread the "
                    "scale arrays through the verify block) — set "
                    "TPU_SPEC_DECODE=off")
            self.kv_scale_granule = granule_dim(kv_quant_granule,
                                                model_cfg.num_kv_heads)
        else:
            self.kv_scale_granule = 0
        # Extra _note_compile attrs for cache-touching programs: the
        # quantized tier's executables get their own ledger keys, the
        # bf16 tier's keys stay byte-identical to before.
        self._kvq_attrs = {"kv_quant": "int8"} if self.kv_quant else {}
        if self.weight_quant == "int4":
            # Int4 executables get their own ledger keys; the off/int8
            # tiers' keys stay byte-identical to before this tier
            # existed (the acceptance bar for WEIGHT_QUANT=off).
            self._kvq_attrs = dict(self._kvq_attrs,
                                   weight_quant="int4")
        # Paged KV tier (KV_LAYOUT=paged — kvcache/blocks.py,
        # docs/KVCACHE.md "Paged tier"): the cache becomes one flat
        # block pool [L, blocks*block_size, Kv, H] and per-slot block
        # tables map logical positions to pool rows, so HBM admission
        # capacity is priced at blocks actually in use instead of
        # every slot's worst-case context. Composes with the int8
        # tier (scales live in pool layout), the host park/offload
        # tier (block-granular entries), speculative + structured
        # decoding (both ride the scatter decode path), and the
        # Pallas decode kernel (block-walking variant). Single-device
        # only, same precedent as shared_prefix/KV_QUANT: the pool
        # and tables are host-orchestrated per chip.
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', "
                             f"got {kv_layout!r}")
        self.paged = kv_layout == "paged"
        self.kv_block_size = int(kv_block_size)
        self._kv_blocks: BlockAllocator | None = None
        if self.paged:
            bs = self.kv_block_size
            if bs < 8 or bs > _KV_BUCKETS[0] or bs & (bs - 1):
                raise ValueError(
                    f"KV_BLOCK_SIZE must be a power of two in "
                    f"[8, {_KV_BUCKETS[0]}], got {bs}")
            if mesh is not None:
                raise ValueError(
                    "KV_LAYOUT=paged is single-device only: the block "
                    "pool and per-slot tables are host-orchestrated "
                    "per chip (no tp/dp/sp mesh yet)")
            if kv_reserve_policy not in ("none", "fixed", "max_tokens"):
                raise ValueError(
                    f"kv_reserve_policy must be none|fixed|max_tokens, "
                    f"got {kv_reserve_policy!r}")
            self.kv_reserve_policy = kv_reserve_policy
            self.kv_reserve_tokens = max(0, int(kv_reserve_tokens))
            # 0 = dense-equivalent pool (same HBM as the dense layout;
            # the factory passes a budget-derived count in production).
            self.kv_pool_blocks = int(kv_pool_blocks) \
                or num_slots * self.max_len // bs
            self._kv_blocks = BlockAllocator(self.kv_pool_blocks, bs,
                                             num_slots)
        # Radix-tree automatic prefix cache (kvcache/radix.py,
        # docs/KVCACHE.md "Automatic prefix cache"): retired/parked
        # sessions donate their clean prefix blocks to a radix tree
        # keyed by chained block hashes; every admission aliases the
        # longest cached chain and prefills only the delta. Requires
        # the paged layout (the tree owns pool blocks) — Config
        # enforces the same cross-check with a named startup error.
        if kv_radix and not self.paged:
            raise ValueError(
                "KV_RADIX_ENABLED=true requires KV_LAYOUT=paged (the "
                "radix prefix cache holds device pool blocks; the "
                "dense layout has no block pool)")
        self.kv_radix = bool(kv_radix)
        self._kv_radix: RadixTree | None = None
        if self.kv_radix:
            token_row_bytes = (2 * model_cfg.num_layers
                               * model_cfg.num_kv_heads
                               * model_cfg.head_dim
                               * (1 if kv_quant == "int8"
                                  else jnp.dtype(dtype).itemsize))
            self._kv_radix = RadixTree(
                self._kv_blocks,
                min_free_blocks=max(0, int(kv_radix_min_blocks)),
                evict_policy=kv_radix_evict_policy,
                token_bytes=token_row_bytes)
            self._kv_blocks.set_pressure(self._kv_radix.evict)
        # Worst-case decode-position advances of in-flight calls
        # (paged only): the dispatcher must pre-allocate blocks for
        # where the DEVICE can be, which leads the host mirrors by
        # these.
        self._paged_leads: deque[int] = deque()
        # Single-device decode uses models.llama.forward_decode: the
        # whole cache rides the step scan's CARRY (carries alias inside
        # a program), each step scatter-writes only the new K/V column,
        # and attention reads a slice bounded by the KV bucket. The r2
        # design sliced the bucket out of the cache and scattered it
        # back around every K-step call; together with the scan-ys
        # recycling inside forward() those copies traced at ~40% of
        # decode wall time on a v5e-1 (measured best structure of five:
        # 3.96 ms/step vs 4.99 classic, llama.py forward_decode note).
        # The mesh path keeps forward(): its cache is "sp"-sharded and
        # per-layer dynamic slices would break GSPMD's even sharding.
        self._scatter_decode = mesh is None
        # Which attention path decode steps actually run — perf
        # attribution only (README perf table "kernel" column,
        # BENCH_MODE=roofline): all four decode families (plain/
        # history/fsm/spec) route through forward_decode's
        # pallas_dense/pallas_paged flags on the scatter path.
        if self.use_pallas_attention:
            self.attention_kernel = ("pallas_paged" if self.paged
                                     else "pallas_dense")
        else:
            self.attention_kernel = ("xla_gather" if self.paged
                                     else "xla_dense")
        # Self-drafting speculative decoding (engine-owned, no second
        # model): drafts come from the slot's own token history via
        # on-device prompt-lookup, a verify block of draft+1 positions
        # runs through forward_decode_multi, and the longest
        # sampled-equal prefix is accepted — exactly
        # distribution-preserving for deterministic drafts (sampling
        # t~p and accepting while t == draft emits accept-prob p(d) and
        # the residual distribution on mismatch). Device-side drafting
        # keeps the call pipeline intact: the host is never in the
        # draft loop, so spec calls pipeline exactly like plain ones.
        #
        # Modes: "ngram" = every call speculative; "auto" = the engine
        # decides per call from its own measured acceptance — spec when
        # the EMA tokens-per-verify clears the measured break-even
        # (docs/SPEC_DECODE.md: a verify block costs ~1.43 plain steps
        # on v5e), plain otherwise, with a periodic probe call so a
        # workload shift (e.g. templated text arriving) is noticed.
        # Auto never loses more than the probe overhead (~1 call in
        # 16) and wins whenever drafts are being accepted — VERDICT r4
        # #3's no-knob-guessing mode.
        # Requires the scatter-decode path. Composes with the Pallas
        # attention kernel: the verify block (T = draft+1 positions)
        # runs through the multi-token q generalisation of the kernel
        # (dense and paged variants), so spec no longer forces
        # TPU_USE_PALLAS_ATTENTION off.
        spec_ok = self._scatter_decode
        self.spec_mode = (spec_decode
                          if spec_ok
                          and spec_decode in ("ngram", "auto") else "off")
        self.spec_draft = (max(1, spec_draft_len)
                           if self.spec_mode != "off" else 0)
        self.spec_breakeven = spec_breakeven
        self._spec_probe_every = 16
        self._spec_probe_countdown = 1  # probe on the first call
        # EMA of tokens emitted per verify block: sizes the dispatcher's
        # token promises and drives the auto-mode decision.
        self._spec_ema = 1.0
        # Cross-session shared-prefix KV: a fresh admission whose prompt
        # starts with rows already resident in ANOTHER slot (the
        # common-system-prompt fleet case) copies those rows in HBM
        # instead of re-prefilling them — a [L, plen, Kv, H] device
        # copy is ~free next to recomputing the prefix through the
        # model. Single-device only: on a mesh the slot axis is
        # "dp"-sharded and a cross-slot dynamic slice would bounce
        # through collectives.
        self.shared_prefix = shared_prefix and mesh is None
        # Structured decoding (fasttalk_tpu/structured/,
        # docs/STRUCTURED.md): per-request grammar/JSON-schema
        # constraints compiled to token FSMs whose allowed-token mask
        # is gathered inside the jitted decode scan. The compatibility
        # matrix is EXPLICIT, following the KV-quant precedent:
        # - single-device only in v1 (the mesh decode path is the
        #   non-scatter forward; per-slot FSM state is not threaded
        #   through it);
        # - the Pallas decode kernel composes (it rides the scatter
        #   path via pallas_dense/pallas_paged);
        # - speculative decoding pauses per CALL while any constrained
        #   slot is running (verify-block masking is unvalidated) and
        #   resumes when the last constrained slot finishes.
        # "auto" degrades to unavailable on incompatible engines
        # (constrained REQUESTS are rejected with the reason; plain
        # serving is untouched); "on" makes the incompatibility a
        # construction error; "off" disables the subsystem.
        if structured not in ("auto", "on", "off"):
            raise ValueError(f"structured must be auto|on|off, "
                             f"got {structured!r}")
        reason: str | None = None
        if mesh is not None:
            reason = ("structured decoding is single-device only in "
                      "v1 (no tp/dp/sp mesh — per-slot FSM state is "
                      "not threaded through the sharded decode path)")
        if structured == "on" and reason is not None:
            raise ValueError(f"STRUCTURED_MODE=on: {reason}")
        if structured == "off":
            reason = "disabled (STRUCTURED_MODE=off)"
        # None = constrained requests are served; a string = the
        # rejection reason (serving layers read this pre-breaker).
        self.structured_reason = reason
        self._st_jf_min = max(0, structured_jf_min)
        self._st_cfg = {"max_states": structured_max_states,
                        "state_budget": structured_state_budget,
                        "cache_size": structured_cache,
                        "json_depth": structured_json_depth}
        self._st_compiler: FSMCompiler | None = None   # lazy (asyncio)
        self._st_compiler_lock = threading.Lock()
        self._st_arena: FSMArena | None = None         # lazy (engine)
        self._st_sample_fn: Any = None
        self._st_patch_fn: Any = None

        if mesh is not None:
            # Tensor-parallel serving: weights and KV sharded over ICI;
            # GSPMD turns the row-parallel matmuls into all-reduces.
            # (The reference's only TP story was forwarding
            # --tensor-parallel-size to an external container,
            # docker-compose.vllm.yml:42.) The cache is created directly
            # in its shards; params are re-placed (a no-op when the
            # loader already put them with parallel.sharding.param_put).
            from fasttalk_tpu.parallel.sharding import (shard_params,
                                                        validate_mesh)
            validate_mesh(mesh, num_kv_heads=model_cfg.num_kv_heads,
                          num_heads=model_cfg.num_heads,
                          hidden=model_cfg.hidden_size,
                          intermediate=model_cfg.intermediate_size,
                          vocab=model_cfg.vocab_size,
                          num_slots=num_slots, max_len=self.max_len)
            self.params = shard_params(params, mesh)
        self.cache = self._make_cache()
        self.seed = seed
        # Sampling is restricted to ids the tokenizer can decode: with a
        # real checkpoint the two vocabs match and this is a no-op, but
        # weight-free serving pairs random-init weights (model vocab,
        # e.g. 128256) with the bundled 32k tokenizer — unclamped
        # sampling then emits ~75% undecodable ids, whose empty text
        # deltas hold first-token frames back a whole decode call.
        self.sample_vocab = min(model_cfg.vocab_size,
                                getattr(tokenizer, "vocab_size",
                                        model_cfg.vocab_size))
        # Session KV host-offload tier (docs/KVCACHE.md): a budgeted
        # host-RAM pool parks evicted/idle sessions' kept KV rows so a
        # returning session restores by copy instead of re-prefilling
        # its whole history. Single-device only, like shared_prefix: on
        # a mesh the cache is sharded and a host snapshot would bounce
        # through cross-host collectives. Unset knobs resolve from the
        # KV_* env (Config passes them explicitly in production).
        kvdef = kv_env_defaults()
        budget_mb = kvdef["budget_mb"] if kv_host_budget_mb is None \
            else kv_host_budget_mb
        if mesh is not None:
            budget_mb = 0.0
        self._kv_pool = HostKVPool(
            budget_mb=budget_mb,
            ttl_s=kvdef["ttl_s"] if kv_park_ttl_s is None
            else kv_park_ttl_s)
        self._kv_policy = RestorePolicy(
            min_tokens=int(kvdef["min_tokens"]
                           if kv_restore_min_tokens is None
                           else kv_restore_min_tokens))
        self._kv_offload = KVOffloader(self._kv_pool, self._kv_policy,
                                       tracer=get_tracer())
        self._kv_park_idle_s = kvdef["idle_s"] if kv_park_idle_s is None \
            else kv_park_idle_s
        self._kv_last_tick = 0.0
        self.slots = SlotManager(num_slots, self.max_len,
                                 on_evict=self._park_on_evict,
                                 on_unpin=self._on_slot_unpin)
        self.steps_per_call = max(1, steps_per_call)
        # Burst-mode call length: while admissions or prefills are
        # pending, dispatch SHORT calls so a new arrival's prefill waits
        # behind one short call in the in-order device queue instead
        # of pipeline_depth long ones (long calls amortise per-call
        # cost, which is what steady-state wants; TTFT under
        # concurrent load wants the opposite).
        self.steps_burst = min(8, self.steps_per_call)
        self.pipeline_depth = max(1, pipeline_depth)
        self.sampling_method = sampling_method
        # Device→host copies run on a small worker pool, submitted at
        # dispatch time, so fetches overlap both each other and later
        # calls' compute, and retirement only ever waits on the oldest
        # outstanding copy. Workers only read result arrays the engine
        # never mutates; all dispatch stays on the engine thread.
        self._fetch_pool = ThreadPoolExecutor(
            max_workers=max(4, self.pipeline_depth + 2),
            thread_name_prefix="tpu-fetch")
        # Outstanding device→host fetch futures (self._fetch). Tracked
        # independently of _inflight/_pending_firsts because
        # _abort_all clears those deques on a crash — restart() must
        # still be able to QUIESCE the copies before it drops the
        # cache refs (see the restart note).
        self._fetch_pending: set[Future] = set()
        self._reset_decode_state()

        # Multi-host SPMD serving (parallel/spmd_serving.py): when set,
        # every serving-time device call publishes a replay descriptor
        # BEFORE dispatching, so follower processes execute the same
        # program sequence against their shards. Leader-only decision
        # making; followers never start() an engine thread.
        self.call_sink: Any = None

        self._commands: queue.Queue = queue.Queue()
        # Admission control replaces the r1 unbounded FIFO `_waiting`
        # list: bounded queue, priority classes, per-session fairness,
        # deadlines, shed-with-retry_after, graceful drain
        # (scheduling/scheduler.py, docs/SCHEDULING.md). Submissions go
        # straight into the scheduler from the asyncio side (so shed
        # decisions are synchronous); the engine thread pops.
        self._slo = get_slo()
        self._events = get_events()
        self._sched = RequestScheduler(
            queue_bound=queue_bound,
            default_deadline_s=default_deadline_s,
            bulk_aging_s=bulk_aging_s, slots=num_slots,
            # SLO-aware shedding (docs/OBSERVABILITY.md): while the
            # interactive class is page-burning, incoming bulk is shed
            # at the door so capacity goes to the broken promise.
            slo_gate=self._slo.should_shed)
        # Engine-loop heartbeat (observability/watchdog.py): stamped
        # once per loop iteration; a stale stamp with pending work is a
        # hung step (blocked device call) the watchdog turns into a
        # detected, logged, recoverable incident.
        self._hb_mono: float | None = None
        self._prefilling: list[_PrefillState] = []  # long prompts, FIFO
        self._running: dict[int, _Request] = {}  # slot index -> request
        self._by_id: dict[str, _Request] = {}
        self._release_after: set[str] = set()  # sessions to unpin on finish
        self._thread: threading.Thread | None = None
        self._stopped = threading.Event()
        self._started = False
        # Serializes shutdown vs. supervised restart: without it a
        # restart running on an executor thread could observe
        # _started=False mid-shutdown and spawn a fresh engine thread
        # after the process believes the engine is down.
        self._lifecycle_lock = threading.Lock()
        # Serializes terminal-state races between the engine thread
        # (_finish) and the watchdog thread (force_fail): the
        # stall-fail flag set and the SLO recorded-once check must be
        # atomic or a request finishing at the instant it is
        # force-failed double-records its SLO sample.
        self._term_lock = threading.Lock()
        self._closed = False
        self._decode_fns: dict[int, Any] = {}
        self._prefill_fns: dict[int, Any] = {}
        self._spec_fns: dict[tuple, Any] = {}
        self._patch_fn: Any = None
        self._hist_patch_fns: dict[int, Any] = {}
        self._sample_place_fn: Any = None

        m = get_metrics()
        self._m_tokens = m.counter("engine_tokens_generated_total",
                                   "tokens generated by the engine")
        self._m_requests = m.counter("engine_requests_total",
                                     "generation requests accepted")
        self._m_ttft = m.histogram("engine_ttft_ms", "time to first token")
        self._m_step = m.histogram(
            "engine_decode_wait_ms",
            "host blocking wait per retired K-step decode call "
            "(near zero when retirement overlaps the next call)",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1000, 4000))
        self._m_prefill = m.histogram(
            "engine_prefill_ms", "prefill wall time per request",
            buckets=(4, 16, 64, 256, 1000, 4000, 16000, 60000))
        self._m_active = m.gauge("engine_active_slots", "slots decoding")
        self._m_queue = m.gauge("engine_queue_depth", "requests waiting")
        self._m_prefix = m.counter("engine_prefix_tokens_reused_total",
                                   "prompt tokens served from resident KV")
        self._m_shared = m.counter(
            "engine_shared_prefix_tokens_total",
            "prompt tokens served by cross-slot KV copy instead of "
            "prefill")
        self._m_spec = m.histogram(
            "engine_spec_tokens_per_verify",
            "tokens emitted per speculative verify block (accepted "
            "drafts + 1); 1 means no draft accepted",
            buckets=tuple(range(1, max(2, self.spec_draft + 2))))
        # Structured decoding (docs/STRUCTURED.md): volume, the
        # jump-forward savings (tokens emitted without model steps),
        # and validity-contract violations (must stay 0).
        self._m_st_requests = m.counter(
            "structured_requests_total",
            "constrained (structured-output) generations accepted")
        self._m_st_jump = m.counter(
            "structured_jump_forward_tokens_total",
            "forced tokens emitted by jump-forward without decode "
            "steps")
        # Request-phase histograms (ISSUE 1): where a request's latency
        # lives, as aggregates; the span tracer carries the per-request
        # breakdown.
        self._m_queue_wait = m.histogram(
            "queue_wait_ms",
            "wait from request submit to slot admission")
        self._m_prefill_req = m.histogram(
            "prefill_ms",
            "prefill wall time per request, admission to first-token "
            "sample", buckets=(4, 16, 64, 256, 1000, 4000, 16000, 60000))
        self._m_intertok = m.histogram(
            "inter_token_ms",
            "gap between consecutive tokens of one request",
            buckets=(0.5, 1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1000,
                     4000))
        self._tracer = get_tracer()
        # Journey stamps (observability/journey.py): monotonic marks
        # taken around the blocking device fetch of the CURRENT
        # retirement, attached per-request in _flush_emit when the
        # request opted in. One pair per retirement, not per request.
        self._j_wait0: float = 0.0
        self._j_fetched: float = 0.0
        # Attribution ledger (observability/perf.py): binds the served
        # model's FLOP cost estimate so step records can carry per-call
        # FLOPs and /perf can report achieved-vs-peak MFU. The KV
        # element size feeds the ledger's FLOP/byte and KV-bandwidth
        # figures honestly — int8 rows + scales, never an assumed bf16.
        # Bytes one decode step reads per (slot, position) row across
        # all layers: k+v rows, plus the scale rows when quantized.
        kv_elt = 1 if self.kv_quant else jnp.dtype(dtype).itemsize
        self._kv_row_bytes = 2 * model_cfg.num_layers * (
            model_cfg.num_kv_heads * model_cfg.head_dim * kv_elt
            + self.kv_scale_granule * 4)
        # Weight bytes one decode step streams from HBM: every resident
        # leaf is read once per step — except an UNTIED embedding, which
        # the step only gathers a few rows of (the tied table doubles as
        # the head matmul and is streamed in full). Summing actual leaf
        # nbytes keeps the figure honest per tier: bf16 arrays, int8
        # {"q","s"} and int4 {"q4","s"} dicts alike, scales included.
        def _tree_bytes(t: Any) -> int:
            return int(sum(x.nbytes
                           for x in jax.tree_util.tree_leaves(t)))

        self._weight_bytes_per_step = _tree_bytes(params)
        if "lm_head" in params:
            self._weight_bytes_per_step -= _tree_bytes(params["embed"])
        self._perf = get_perf()
        self._perf.bind_model(model_cfg, num_slots,
                              jnp.dtype(dtype).name,
                              kv_quant=kv_quant,
                              kv_row_bytes=self._kv_row_bytes,
                              weight_quant=self.weight_quant,
                              weight_bytes_per_step=(
                                  self._weight_bytes_per_step),
                              attention_kernel=self.attention_kernel,
                              devices=self._devices())

    def _make_cache(self) -> KVCache:
        if self.paged:
            return init_paged_cache(
                self.cfg, self.kv_pool_blocks, self.kv_block_size,
                self.dtype, quantized=self.kv_quant,
                scale_granule=max(1, self.kv_scale_granule))
        if self.mesh is None:
            return init_cache(self.cfg, self.num_slots, self.max_len,
                              self.dtype, quantized=self.kv_quant,
                              scale_granule=max(1,
                                                self.kv_scale_granule))
        from jax.sharding import NamedSharding

        from fasttalk_tpu.parallel.sharding import cache_pspecs

        return init_cache(self.cfg, self.num_slots, self.max_len, self.dtype,
                          device=NamedSharding(self.mesh, cache_pspecs().k))

    def _reset_decode_state(self) -> None:
        """(Re)build the host mirrors and device-resident decode state."""
        num_slots = self.num_slots
        # Host mirrors of the per-slot decode state. The authoritative
        # copies live on the device and chain through decode calls; slot
        # changes are scattered onto them with _patch_slot_state.
        self._positions = np.zeros((num_slots,), np.int32)
        self._active_mask = np.zeros((num_slots,), bool)
        self._temps = np.zeros((num_slots,), np.float32)
        self._topks = np.zeros((num_slots,), np.int32)
        self._topps = np.ones((num_slots,), np.float32)
        self._reps = np.ones((num_slots,), np.float32)
        self._press = np.zeros((num_slots,), np.float32)
        self._freqs = np.zeros((num_slots,), np.float32)
        self._cur_tokens = self._put(np.zeros((num_slots,), np.int32))
        self._positions_dev = self._put(self._positions)
        self._active_dev = self._put(self._active_mask)
        self._temps_dev = self._put(self._temps)
        self._topks_dev = self._put(self._topks)
        self._topps_dev = self._put(self._topps)
        self._reps_dev = self._put(self._reps)
        self._press_dev = self._put(self._press)
        self._freqs_dev = self._put(self._freqs)
        # Per-slot emitted-token counts [S, sample_vocab] — the penalty
        # state (ops/sampling.apply_penalties). Maintained in-program by
        # the decode steps (each step counts the token it FEEDS, so every
        # emitted token — including the prefill-sampled first — is
        # counted exactly once); zeroed by the patch program when a slot
        # is (re)admitted or finishes. At [16, 128k] int32 this is ~8 MB.
        self._counts_dev = self._put(
            np.zeros((num_slots, self.sample_vocab), np.int32))
        self._rng_dev = self._put(jax.random.PRNGKey(self.seed))
        # Speculative decoding's device-resident token history
        # [S, max_len]: the draft source. Chained through spec calls
        # (accepted tokens appended in-program); prompt tokens are
        # uploaded at admission via _patch_slot_state. int32, ~KBs.
        self._history_dev = (self._put(
            np.zeros((num_slots, self.max_len), np.int32))
            if self.spec_draft else None)
        # slot index -> prompt token list awaiting history upload.
        self._dirty_history: dict[int, list[int]] = {}
        # Slots whose host mirrors changed since the last device patch.
        # Changes are SCATTERED onto the chained device arrays instead of
        # draining the pipeline and re-uploading everything — admission
        # and completion never stall in-flight decode calls.
        self._dirty_slots: set[int] = set()
        # In-flight decode calls: (host-copy Future, EXPECTED tokens the
        # call will emit per request, EXPECTED positions it advances,
        # the (slot index, request) pairs running at dispatch time,
        # dispatch timestamp for step telemetry, KV bucket length —
        # the attribution ledger's attention-cost horizon).
        # Plain calls emit exactly K tokens (both fields == K);
        # speculative calls emit K..K*(G+1) and both fields are
        # EMA-based estimates — the dispatcher's base/bucket math may
        # therefore transiently under- or over-estimate device
        # positions, which is safe: the in-call act gate masks steps
        # that would overflow the chosen bucket, and retirement re-syncs
        # the host mirrors (one under-productive call worst case; never
        # a correctness issue). Tokens are attributed to the
        # dispatch-time request, never to whoever occupies the slot at
        # retirement — a slot can be re-admitted to a new request while
        # an older call is still in flight.
        self._inflight: deque[
            tuple[Future, float, int, list[tuple[int, _Request]],
                  float, int, str]] = deque()
        # First sampled tokens whose device→host copy is still in
        # flight: (host-copy Future, [(row, slot_index, request), ...]).
        # Admission emits the first token only when the fetch lands, so
        # prefill never blocks the engine thread on a device round trip.
        self._pending_firsts: deque[tuple[Future, list]] = deque()
        # Structured decoding device state (docs/STRUCTURED.md): the
        # per-slot FSM state vector is chained through constrained
        # decode calls exactly like positions; 0 = the FREE state every
        # unconstrained slot sits in. The union tables (masks/cls/next)
        # upload at admission when the arena grows — never per step.
        self._st_state_dev = self._put(np.zeros((num_slots,), np.int32))
        self._st_sel = np.zeros((num_slots,), np.int32)  # host mirror
        self._st_masks_dev: Any = None
        self._st_cls_dev: Any = None
        self._st_nexts_dev: Any = None
        self._st_dirty: set[int] = set()       # slots needing st patch
        self._st_jf_pending: set[str] = set()  # request ids to jump
        if self._st_arena is not None:
            self._st_arena.dirty = True        # restart: re-upload

    # ---------------- public (asyncio side) ----------------

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        self._stopped.clear()
        self._thread = threading.Thread(target=self._run, name="tpu-engine",
                                        daemon=True)
        self._thread.start()

    def shutdown(self, timeout_s: float = 30.0) -> None:
        with self._lifecycle_lock:
            self._closed = True
            if self._started:
                self._commands.put(("stop", None))
                if not self._stopped.wait(timeout=timeout_s):
                    # The engine thread is stuck (a wedged device call,
                    # a hung collective): we are about to leak it —
                    # say WHERE it is stuck instead of leaking
                    # silently. sys._current_frames gives the exact
                    # frame the thread is blocked in.
                    self._log_stuck_thread(timeout_s)
                self._started = False
            self._fetch_pool.shutdown(wait=False, cancel_futures=True)
            self._kv_offload.shutdown()
            if self._st_compiler is not None:
                self._st_compiler.shutdown()

    def _log_stuck_thread(self, timeout_s: float) -> None:
        """Shutdown timed out: capture the stuck engine thread's stack
        (sys._current_frames) into the log and a critical event, so
        the leaked thread is a diagnosed incident instead of a silent
        one. faulthandler-style, but scoped to the one thread and
        delivered through the event log the flight recorder bundles."""
        import sys
        import traceback

        thread = self._thread
        stack = ""
        if thread is not None and thread.ident is not None:
            frame = sys._current_frames().get(thread.ident)
            if frame is not None:
                stack = "".join(traceback.format_stack(frame))
        log.critical(
            f"engine thread failed to stop within {timeout_s:.0f}s; "
            f"leaking it. Stuck at:\n{stack or '<thread already gone>'}")
        self._events.emit("engine_shutdown_stuck", severity="critical",
                          timeout_s=timeout_s,
                          stack=stack[-2000:] if stack else "")

    def restart(self) -> bool:
        """Recover from an engine-thread crash: rebuild the device-side
        decode state (the crash may have struck mid-call, leaving the
        donated cache buffer consumed or poisoned) and start a fresh
        thread on the SAME command queue, so requests submitted during
        the outage are served rather than lost. Session KV residency is
        dropped — a session's next turn re-prefills — but the process
        keeps serving, where the reference's only recovery was a
        container restart (docker restart: unless-stopped,
        docker-compose.vllm.yml:14). Compiled executables are kept:
        weights are intact, so nothing needs recompiling."""
        with self._lifecycle_lock:
            if self._closed:
                return False  # shutdown won; never resurrect past it
            if self.call_sink is not None:
                # Restart is leader-local device-state surgery and is
                # not replicated to followers; multi-host recovery is a
                # cluster restart (parallel/spmd_serving.py scope note).
                log.error("engine restart unsupported in multi-host "
                          "SPMD serving mode")
                return False
            if self.check_connection():
                return True
            if self._thread is not None and self._thread.is_alive():
                return False  # still tearing down; try again later
            log.warning("engine restart: rebuilding device decode state")
            # Parked host KV intentionally SURVIVES the restart: the
            # pool holds host memory only, so sessions whose device
            # residency the crash destroyed still restore their kept
            # prefix instead of re-prefilling the whole history —
            # recovery costs one H2D copy per returning session, not
            # O(history) recompute (docs/KVCACHE.md).
            self._events.emit("engine_restart", severity="critical",
                              parked_sessions=len(self._kv_pool))
            # Entries whose requests were terminal-errored by
            # _abort_all must not be re-admitted; entries submitted in
            # the crash race window (after the sweep) survive and the
            # new thread will admit them.
            self._sched.remove_finished()
            self._prefilling.clear()
            self._running.clear()
            self._release_after.clear()
            # Keep registrations of requests submitted in the crash race
            # window (registered after _abort_all's sweep): their queued
            # submit commands survive on the shared command queue and the
            # new thread will admit them — dropping the registration
            # would strand cancel() for those ids. Prune IN PLACE (not a
            # dict rebuild): generate() on the event loop can insert a
            # registration concurrently, and a rebuild would silently
            # drop it (ADVICE r2) — per-key pops never lose an insert.
            for rid in [rid for rid, r in self._by_id.items()
                        if r.finished]:
                self._by_id.pop(rid, None)
            self.slots = SlotManager(self.num_slots, self.max_len,
                                     on_evict=self._park_on_evict,
                                     on_unpin=self._on_slot_unpin)
            if self.paged:
                # The crash may have struck mid-allocation; the pool is
                # rebuilt with the cache (all sessions re-prefill, so
                # no table survives either).
                self._kv_blocks = BlockAllocator(
                    self.kv_pool_blocks, self.kv_block_size,
                    self.num_slots)
                if self._kv_radix is not None:
                    # Cached prefix rows died with the cache: rebuild
                    # the tree empty over the fresh pool (holds in the
                    # old tree point at the discarded allocator).
                    self._kv_radix = RadixTree(
                        self._kv_blocks,
                        min_free_blocks=self._kv_radix.min_free_blocks,
                        evict_policy=self._kv_radix.evict_policy,
                        token_bytes=self._kv_radix.token_bytes)
                    self._kv_blocks.set_pressure(self._kv_radix.evict)
            self._paged_leads.clear()
            # Quiesce the fetch workers FIRST: the crashed thread's
            # in-flight device calls may still be executing on the
            # async dispatch stream with their host copies mid-flight
            # on the fetch pool (_abort_all cleared the deques, not
            # the workers). Dropping the only cache/decode-state refs
            # while the runtime still reads those buffers corrupts
            # the heap (observed: malloc corruption in back-to-back
            # crash→restart chaos drills on the XLA-CPU client).
            # A landed fetch implies its producing call retired on
            # the in-order dispatch stream.
            from concurrent.futures import TimeoutError as _FutTimeout

            for fut in list(self._fetch_pending):
                try:
                    fut.result(timeout=10)
                except _FutTimeout:
                    # The copy is STILL RUNNING: dropping the only
                    # cache/decode-state refs now is exactly the
                    # use-after-free this quiesce prevents. Refuse
                    # this attempt — the supervisor retries (with
                    # backoff), and a permanently wedged copy exhausts
                    # the restart budget into the designed /health-
                    # dead state instead of corrupting the heap.
                    log.error("engine restart aborted: a device->host "
                              "copy is still in flight after 10s")
                    return False
                except Exception:
                    pass  # the copy FAILING is fine; gone is gone
            try:
                # Sync the in-order dispatch stream on the cache chain
                # itself: the last dispatched call's donated-cache
                # output must exist before we drop its only reference.
                jax.block_until_ready(self.cache.k)
            except Exception:
                pass  # a poisoned cache buffer is being replaced anyway
            # Release the old KV cache (and the in-flight refs pinning
            # decode-state arrays) BEFORE allocating the fresh one: on
            # host-side crashes the donated buffer was never consumed,
            # and holding both copies transiently doubles KV HBM — on
            # memory-tight configs the recovery path itself would OOM
            # and the watchdog would re-OOM every probe (ADVICE r2).
            self.cache = None
            self._inflight.clear()
            self._pending_firsts.clear()
            self.cache = self._make_cache()
            self._reset_decode_state()
            self._started = False
            self.start()
            return self.check_connection()

    def warmup(self, level: str = "fast") -> None:
        """Compile hot shapes before serving traffic, so the first users
        never pay the 20-40s XLA compile (the reference's analogue was
        the engine container's multi-minute cold start behind a 300s
        health start_period, docker-compose.vllm.yml:62-67).

        Must run before ``start()`` (single-threaded device access).
        ``fast`` compiles the common chat shapes (~6 executables): the
        first decode KV bucket, batched prefill at the typical prompt
        bucket and the configured chunk for group sizes {1, num_slots},
        plus the single-slot long-prompt path at the full chunk size
        (one long system prompt is common in voice deployments).
        ``full`` adds every decode KV bucket up to max_len and every
        prefill bucket. Warmup
        calls mask their writes (or, for the single-slot path, write
        into a slot region no session has claimed yet), so no later
        request can observe warmup garbage.
        """
        if level in ("off", "", "none"):
            return
        if self._started:
            raise RuntimeError("warmup() must be called before start()")
        if self.call_sink is not None:
            # Warmup calls are not published to followers; multi-host
            # serving compiles lazily on both sides instead.
            raise RuntimeError(
                "warmup is unsupported with a multi-host call sink "
                "attached (set TPU_WARMUP=off)")
        t0 = time.monotonic()
        kv_buckets = [b for b in _KV_BUCKETS if b <= self.max_len] \
            or [self.max_len]
        # Serving picks buckets from _PREFILL_BUCKETS with b >= chunk, so
        # a sub-16 prefill_chunk still lands on the smallest bucket.
        pbuckets = [b for b in _PREFILL_BUCKETS
                    if b <= self.prefill_chunk] or [_PREFILL_BUCKETS[0]]
        if level != "full":
            common = 64 if 64 in pbuckets else pbuckets[0]
            # Include the long-prompt chunk bucket so the fast warmup's
            # single-slot compile below actually triggers.
            chunk_bucket = next((x for x in _PREFILL_BUCKETS
                                 if x >= self.prefill_chunk),
                                _PREFILL_BUCKETS[-1])
            pbuckets = sorted({common, pbuckets[-1], chunk_bucket})
        decode_buckets = kv_buckets if level == "full" else kv_buckets[:1]

        inactive = self._put(np.zeros((self.num_slots,), bool))
        for b in decode_buckets:
            for steps in sorted({self.steps_burst, self.steps_per_call}):
                if self.spec_draft:
                    # Spec modes dispatch the history-maintaining plain
                    # variant (the no-history one is never used).
                    fn = self._get_decode_fn(b, steps, with_history=True)
                    (self.cache, self._history_dev, self._counts_dev,
                     toks, _, _, _) = fn(
                        self.params, self.cache, self._history_dev,
                        self._counts_dev, self._cur_tokens,
                        self._positions_dev, inactive, self._temps_dev,
                        self._topks_dev, self._topps_dev,
                        self._reps_dev, self._press_dev,
                        self._freqs_dev, self._rng_dev,
                        *self._paged_decode_args(b))
                else:
                    fn = self._get_decode_fn(b, steps)
                    self.cache, self._counts_dev, toks, _, _, _ = fn(
                        self.params, self.cache, self._counts_dev,
                        self._cur_tokens, self._positions_dev, inactive,
                        self._temps_dev, self._topks_dev,
                        self._topps_dev, self._reps_dev,
                        self._press_dev, self._freqs_dev, self._rng_dev,
                        *self._paged_decode_args(b))
                jax.block_until_ready(toks)
                if self.spec_draft:
                    # All-inactive spec warmup: every write masks out.
                    # No eligibility gate here — dispatch eligibility
                    # depends on runtime positions (EMA-sized need),
                    # so any gate that skips a (bucket, steps) pair
                    # warmup-time can still see it requested mid-stream
                    # and pay the compile under traffic.
                    sfn = self._get_spec_decode_fn(b, steps)
                    (self.cache, self._history_dev, self._counts_dev,
                     toks, _, _, _) = sfn(
                        self.params, self.cache, self._history_dev,
                        self._counts_dev, self._cur_tokens,
                        self._positions_dev, inactive,
                        self._temps_dev, self._topks_dev,
                        self._topps_dev, self._reps_dev, self._press_dev,
                        self._freqs_dev, self._rng_dev,
                        *self._paged_decode_args(b))
                    jax.block_until_ready(toks)
        if self.spec_draft:
            # The admission-path history upload (slot indices out of
            # range: every row drops). 256 is the common chat-prompt
            # row bucket; longer prompts compile their bucket on first
            # use (a tiny pad+scatter program).
            self._history_dev = self._get_hist_patch_fn(
                min(256, self.max_len))(
                self._history_dev,
                self._arg(np.zeros((self.num_slots,
                                    min(256, self.max_len)), np.int32)),
                self._arg(np.full((self.num_slots,), self.num_slots,
                                  np.int32)))
            jax.block_until_ready(self._history_dev)
        # The admission-path helper programs (slot-state patch; they are
        # tiny but a first-request compile is still seconds).
        nopatch = np.zeros((self.num_slots, 9), np.float32)
        (self._counts_dev, self._positions_dev, self._active_dev,
         self._temps_dev, self._topks_dev, self._topps_dev,
         self._reps_dev, self._press_dev, self._freqs_dev) = \
            self._get_patch_fn()(
                self._arg(nopatch), self._counts_dev, self._positions_dev,
                self._active_dev, self._temps_dev, self._topks_dev,
                self._topps_dev, self._reps_dev, self._press_dev,
                self._freqs_dev)

        # The single-slot long-prompt path buckets by the smallest
        # _PREFILL_BUCKETS entry covering a full chunk — warm exactly
        # that shape (pbuckets[-1] only equals it when prefill_chunk is
        # itself a bucket value).
        long_bucket = next((x for x in _PREFILL_BUCKETS
                            if x >= self.prefill_chunk), _PREFILL_BUCKETS[-1])
        for b in pbuckets:
            # Must match the ctx _prefill_group derives for a fresh
            # session (starts=0): the smallest KV bucket covering b.
            ctx = next((k for k in kv_buckets if k >= b), self.max_len)
            for gp in sorted({1, self.num_slots}):
                # All rows masked + out-of-range scatter: no cache (or
                # cur-token) writes. Args are built exactly as the
                # serving path builds them (numpy via _arg) so the
                # compiled executable keys on the same avals.
                rowcfg = np.zeros((gp, 7), np.float32)
                rowcfg[:, 0] = np.arange(self.num_slots,
                                         self.num_slots + gp)
                rowcfg[:, 4:] = (1.0, 40, 0.9)
                if self.paged:
                    fn = self._get_paged_batched_prefill_fn(b, gp, ctx)
                    widx = np.stack([self._paged_oob_indices(j, b)
                                     for j in range(gp)])
                    (self.cache, firsts, self._cur_tokens,
                     self._rng_dev) = fn(
                        self.params, self.cache,
                        self._arg(np.zeros((gp, b), np.int32)),
                        self._arg(rowcfg),
                        self._arg(np.zeros((gp, ctx), np.int32)),
                        self._arg(widx), self._cur_tokens,
                        self._rng_dev)
                else:
                    fn = self._get_batched_prefill_fn(b, gp, ctx)
                    (self.cache, firsts, self._cur_tokens,
                     self._rng_dev) = fn(
                        self.params, self.cache,
                        self._arg(np.zeros((gp, b), np.int32)),
                        self._arg(rowcfg), self._cur_tokens,
                        self._rng_dev)
                jax.block_until_ready(firsts)
            if level == "full" or b == long_bucket:
                # Single-slot long-prompt path: writes land in slot 0's
                # region, unclaimed at warmup time (kv_written stays 0,
                # so nothing ever trusts them). Its first-token sample
                # runs the same jitted sample-and-place program the
                # serving path uses (slot index out of range: the
                # current-token scatter drops).
                if self.paged:
                    wctx = next((k for k in kv_buckets if k >= b),
                                self.max_len)
                    fn = self._get_paged_prefill_fn(b, wctx)
                    self.cache, last = fn(
                        self.params, self.cache,
                        self._arg(np.zeros((b,), np.int32)),
                        np.int32(0),
                        self._arg(np.zeros((wctx,), np.int32)),
                        self._arg(self._paged_oob_indices(0, b)),
                        np.int32(b - 1))
                else:
                    fn = self._get_prefill_fn(b)
                    self.cache, last = fn(
                        self.params, self.cache,
                        self._arg(np.zeros((b,), np.int32)),
                        np.int32(0), np.int32(0), np.int32(b - 1))
                cfg_row = np.array([self.num_slots, 1.0, 40, 0.9],
                                   np.float32)
                first, self._cur_tokens, self._rng_dev = \
                    self._get_sample_place_fn()(
                        last, self._cur_tokens, self._rng_dev,
                        self._arg(cfg_row))
                jax.block_until_ready(first)
        if self._kv_pool.enabled:
            # Host-offload copy programs (kvcache/offload.py): compile
            # every power-of-two bucket now, so no park/restore ever
            # pays a mid-traffic compile stall (the shapes are trivial
            # slice/update programs — cheap next to the model graphs
            # above). The warmup restore writes zero rows into slot 0,
            # which nothing has claimed yet (kv_written stays 0).
            b = max(16, self.kv_block_size) if self.paged else 16
            while True:
                # Slice returns (k, v) — or (k, v, k_scale, v_scale)
                # on the quantized tier — in exactly the restore fn's
                # argument order, so the round trip is layout-agnostic.
                if self.paged:
                    # Gather pool row 0, scatter to dropped OOR rows:
                    # the paged copy programs compile with no writes.
                    rows = self._get_paged_kv_slice_fn(b)(
                        self.cache,
                        self._arg(np.zeros((b,), np.int32)))
                    self.cache = self._get_paged_kv_restore_fn(b)(
                        self.cache, *rows,
                        self._arg(self._paged_oob_indices(0, b)))
                else:
                    rows = self._get_kv_slice_fn(b)(
                        self.cache, np.int32(0))
                    self.cache = self._get_kv_restore_fn(b)(
                        self.cache, *rows, np.int32(0))
                jax.block_until_ready(self.cache.k)
                if b >= self.max_len:
                    break
                b = min(b * 2, self.max_len)
        if self.shared_prefix:
            # Shared-prefix stamp programs at the common granules (the
            # quantized tier's variants copy rows + scales): a cold
            # fleet burst's first admission should not pay this compile
            # on the TTFT path. Src == dst == slot 0 (unclaimed at
            # warmup; kv_written stays 0, so nothing trusts the rows).
            # Paged tier: sharing is block ALIASING (host bookkeeping,
            # nothing to compile) — only the single COW block-copy
            # program warms, src == dst == block 0.
            if self.paged:
                self.cache = self._get_block_copy_fn()(
                    self.cache, np.int32(0), np.int32(0))
            else:
                for plen in {g for g in (64, 256) if g <= self.max_len}:
                    self.cache = self._get_prefix_copy_fn(plen)(
                        self.cache, np.int32(0), np.int32(0),
                        np.int32(0))
            jax.block_until_ready(self.cache.k)
        jax.block_until_ready(self.cache.k)
        # Warm every fetch worker's first device→host copy: a thread's
        # FIRST fetch can pay one-time client setup, which the first
        # real generation would otherwise absorb as TTFT.
        futs = [self._fetch_pool.submit(np.asarray, self._cur_tokens)
                for _ in range(self._fetch_pool._max_workers)]
        for f in futs:
            f.result()
        log.info(f"warmup({level}) compiled "
                 f"{len(self._decode_fns) + len(self._prefill_fns)} "
                 f"executables in {time.monotonic() - t0:.1f}s")
        if self.weight_quant != "off":
            log.info(f"quantized matmul kernels traced "
                     f"(attention: {self.attention_kernel}): "
                     f"{traced_paths()}")

    async def generate(self, request_id: str, session_id: str,
                       messages: list[dict], params: GenerationParams,
                       ) -> AsyncGenerator[dict, None]:
        """Stream events: {"type": "token", "text": ...} per delta, then a
        terminal {"type": "done"|"error"|"cancelled", ...}."""
        if not self.check_connection():
            raise LLMServiceError("Engine is not running (call start())",
                                  category=ErrorCategory.CONNECTION,
                                  recoverable=True)
        if self.role == "prefill" and not params.prefill_only:
            # Disaggregated prefill tier: this replica exists to run
            # long prefills with zero decode-slot occupancy — a decode
            # stream admitted here would recreate exactly the
            # interference the role split removes. The router never
            # places normal streams here; this is the engine-side
            # guarantee behind that.
            raise LLMServiceError(
                "replica role is 'prefill': decode streams are "
                "rejected (only prefill_only handoff requests admit)",
                category=ErrorCategory.VALIDATION, recoverable=False)
        if params.prefill_only and not self._kv_pool.enabled:
            raise LLMServiceError(
                "prefill_only requires the host KV pool "
                "(KV_HOST_BUDGET_MB > 0): the finished prefill is "
                "parked there for the decode-tier handoff",
                category=ErrorCategory.VALIDATION, recoverable=False)
        if params.raw_prompt:
            # Raw text-completion path (/v1/completions): BOS + verbatim
            # tokens, no chat template (matching vLLM's completions
            # endpoint, which prepends BOS by default).
            prompt = self.tokenizer.encode_prompt(raw_prompt_text(messages))
        else:
            prompt = self.tokenizer.apply_chat_template(messages)
        if len(prompt) >= self.usable_len:
            raise LLMServiceError(
                f"Prompt of {len(prompt)} tokens exceeds context window "
                f"{self.usable_len}", category=ErrorCategory.VALIDATION,
                recoverable=False)
        req = _Request(
            request_id=request_id, session_id=session_id,
            prompt_tokens=prompt, params=params,
            out_queue=asyncio.Queue(), loop=asyncio.get_running_loop(),
            detok=StreamDetokenizer(self.tokenizer))
        if params.structured is not None:
            # Compile (or cache-hit) the token FSM OFF the engine
            # thread and off this event loop, before submission —
            # admission never blocks on a cold schema. Compat and
            # compile failures are client-shape errors: 400 /
            # invalid_config, never a 500 or a breaker hit.
            if self.structured_reason is not None:
                raise LLMServiceError(
                    f"structured output unavailable: "
                    f"{self.structured_reason}",
                    category=ErrorCategory.VALIDATION,
                    recoverable=False)
            if self.call_sink is not None:
                raise LLMServiceError(
                    "structured output is unsupported in multi-host "
                    "SPMD serving mode",
                    category=ErrorCategory.VALIDATION,
                    recoverable=False)
            t0c = time.monotonic()
            try:
                req.fsm = await self._get_st_compiler().compile_async(
                    params.structured)
            except (StructuredError, FSMTooLarge) as e:
                raise LLMServiceError(
                    str(e), category=ErrorCategory.VALIDATION,
                    recoverable=False) from e
            req.fsm_state = req.fsm.start
            self._m_st_requests.inc()
            if self._tracer.enabled:
                self._tracer.add_span(
                    request_id, "fsm_compile", t0c, time.monotonic(),
                    kind=params.structured.get("kind"),
                    states=req.fsm.n_states,
                    classes=req.fsm.n_classes)
        self._m_requests.inc()
        # Trace the request's whole lifecycle. The serving layer starts
        # the trace first (it owns the ws_send spans and the finish);
        # start() returns True only for engine-seam callers (tests,
        # BENCH_MODE=engine), who then own the finish here.
        trace_owned = self._tracer.start(request_id, session_id)
        if self._tracer.enabled:
            self._tracer.set_phase(request_id, "queued",
                                   priority=params.priority)
        # Register before enqueueing so an immediate cancel() can't race
        # the engine thread's command drain.
        self._by_id[request_id] = req
        try:
            # Admission control: bounded queue, deadline-aware,
            # drain-aware. A shed raises AdmissionRejected (with
            # retry_after) synchronously — the caller gets a terminal
            # signal immediately instead of queueing to time out. A
            # session with a parked host-KV entry will skip most of its
            # prefill at admission — the scheduler's wait estimate gets
            # that saving as a discount so the wait_too_long shed
            # doesn't turn away requests the restore makes cheap.
            self._sched.submit(request_id, session_id,
                               priority=params.priority,
                               deadline_s=params.deadline_s, payload=req,
                               wait_discount_s=self._kv_wait_discount(
                                   session_id, prompt)
                               - self._paged_wait_penalty(len(prompt)))
        except AdmissionRejected:
            self._by_id.pop(request_id, None)
            req.finished = True
            self._slo.record_shed(params.priority)
            if self._tracer.enabled:
                self._tracer.event(request_id, "shed")
            if trace_owned:
                self._tracer.finish(request_id)
            raise
        if self._kv_pool.enabled:
            # Best-effort: pre-upload this session's parked KV rows to
            # the device on the copy thread while the request waits in
            # the queue, so the restore at admission dispatches against
            # device-resident arrays (no H2D on the admission path).
            self._kv_offload.prestage(session_id)
        self._commands.put(("kick", None))  # wake the engine thread
        terminal = False
        try:
            while True:
                event = await req.out_queue.get()
                if event["type"] in ("done", "error", "cancelled"):
                    terminal = True
                yield event
                if terminal:
                    return
        finally:
            if not terminal:
                # Caller abandoned the stream (e.g. WebSocket dropped):
                # free the slot instead of decoding to max_tokens.
                self.cancel(request_id)
            if trace_owned:
                self._tracer.finish(request_id)

    def cancel(self, request_id: str) -> bool:
        req = self._by_id.get(request_id)
        if req is None:
            return False
        req.cancelled = True  # visible to the engine thread immediately
        self._commands.put(("cancel", request_id))
        return True

    def release_session(self, session_id: str) -> None:
        self._commands.put(("release", session_id))

    def begin_drain(self) -> None:
        """Stop admitting new submissions (they shed with retry_after);
        queued and in-flight requests run to completion. Used by server
        shutdown so a rolling restart finishes its users' sentences."""
        self._sched.begin_drain()
        if self._started:
            self._commands.put(("kick", None))

    def pending_requests(self) -> int:
        """Requests not yet terminal (queued + prefilling + running):
        the drain loop polls this toward zero."""
        return len(self._by_id)

    def set_trace_component(self, component: str) -> None:
        """Tag this engine's spans with a fleet component name: in-proc
        replicas of a BENCH_MODE=fleet router share ONE process tracer,
        so the component attr is what keeps replica A's prefill/decode
        spans distinguishable from replica B's in a stitched trace."""
        self._tracer = get_tracer().scoped(component)

    def scheduler_debug(self) -> dict:
        """Scheduler state + queued entries (position, priority,
        remaining deadline) + parked host-KV sessions for the
        monitoring port's /debug/requests."""
        return {"stats": self._sched.stats(),
                "queued": self._sched.snapshot(),
                "kv_host": self._kv_pool.stats(),
                "parked_sessions": self._kv_pool.snapshot()}

    # ---------------- watchdog surfaces (observability/watchdog.py) ----

    def heartbeat_age(self, now: float | None = None) -> float | None:
        """Seconds since the engine loop last completed an iteration
        (None before the first one). A large age with pending work
        means the thread is blocked inside a device call."""
        hb = self._hb_mono
        if hb is None:
            return None
        return (time.monotonic() if now is None else now) - hb

    def progress_report(self, now: float | None = None,
                        ) -> list[dict[str, Any]]:
        """Admitted, unfinished requests with how long each has gone
        without forward progress (a token, a prefill chunk, or
        activation). Queued requests are excluded — the scheduler's
        deadline sweep already governs them."""
        now = time.monotonic() if now is None else now
        out: list[dict[str, Any]] = []
        # list() over the dict's values is atomic under the GIL; the
        # engine thread may mutate the dict but never the snapshot.
        for req in list(self._by_id.values()):
            if req.finished or req.admitted_at is None:
                continue
            last = max(filter(None, (req.last_token_at,
                                     req.last_progress_at,
                                     req.admitted_at)))
            out.append({
                "request_id": req.request_id,
                "session_id": req.session_id,
                "phase": "decode" if req.decode_started_at is not None
                else "prefill",
                "no_progress_s": round(now - last, 3),
            })
        return out

    def force_fail(self, request_id: str, error: str,
                   code: str = "stalled") -> bool:
        """Watchdog termination: emit a terminal error frame NOW, from
        outside the engine thread — the whole point is that the engine
        thread may be hung and unable to process a normal cancel. The
        request is also marked cancelled and a cancel command queued,
        so a revived engine thread frees the slot through the ordinary
        _finish path (whose terminal event lands in an already-closed
        stream and is dropped)."""
        req = self._by_id.get(request_id)
        if req is None:
            return False
        with self._term_lock:
            if req.finished or req.stall_failed:
                return False
            req.stall_failed = True
            req.cancelled = True
        self._record_slo(req, ok=False)
        self._emit(req, {"type": "error", "error": error, "code": code})
        self._commands.put(("cancel", request_id))
        return True

    def _record_slo(self, req: _Request, ok: bool) -> None:
        """Feed one finished request into the SLO engine (idempotent —
        the watchdog's force_fail and the engine's _finish can both
        reach a request, from different threads; the terminal lock
        makes the recorded-once check atomic)."""
        with self._term_lock:
            if req.slo_recorded:
                return
            req.slo_recorded = True
        ttft_ms = ((req.first_token_at - req.submitted_at) * 1000.0
                   if req.first_token_at is not None else None)
        qw_ms = ((req.admitted_at - req.submitted_at) * 1000.0
                 if req.admitted_at is not None else None)
        # A single-token reply has no inter-token gap to judge.
        gap_ms = req.max_gap_ms if req.generated >= 2 else None
        self._slo.record_request(req.params.priority, ok=ok,
                                 ttft_ms=ttft_ms, queue_wait_ms=qw_ms,
                                 max_gap_ms=gap_ms)

    def check_connection(self) -> bool:
        return self._started and self._thread is not None \
            and self._thread.is_alive()

    def _devices(self) -> list:
        """The devices this engine computes on: the mesh's, or the
        default device alone — not every device the host has."""
        if self.mesh is not None:
            return list(self.mesh.devices.flat)
        return jax.devices()[:1]

    def get_model_info(self) -> dict:
        devs = self._devices()
        return {
            "model": self.cfg.name,
            "vocab_size": self.cfg.vocab_size,
            "num_layers": self.cfg.num_layers,
            "hidden_size": self.cfg.hidden_size,
            "parameters": self.cfg.param_count(),
            "context_window": self.usable_len,
            "decode_slots": self.num_slots,
            "dtype": jnp.dtype(self.dtype).name,
            "kv_quant": "int8" if self.kv_quant else "none",
            "kv_layout": "paged" if self.paged else "dense",
            "weight_quant": self.weight_quant,
            "attention_kernel": self.attention_kernel,
            # Which implementation each decode-shaped quantized matmul
            # traced to so far (ops/quant.py): "pallas", or "xla:<why>"
            # where supports*() or a flag kept the kernel out.
            "quant_kernels": traced_paths(),
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind,
                       "count": len(devs)},
            "devices": [str(d) for d in devs],
            "mesh": dict(self.mesh.shape) if self.mesh is not None else None,
        }

    def get_stats(self) -> dict:
        structured: dict[str, Any] = {
            "available": self.structured_reason is None,
        }
        if self.structured_reason is not None:
            structured["reason"] = self.structured_reason
        if self._st_compiler is not None:
            structured["compiler"] = self._st_compiler.stats()
        if self._st_arena is not None:
            structured["arena"] = self._st_arena.stats()
        out = {
            "slots": self.slots.stats(),
            "waiting": len(self._sched),
            "scheduler": self._sched.stats(),
            "running": len(self._running),
            "kv_quant": "int8" if self.kv_quant else "none",
            "kv_layout": "paged" if self.paged else "dense",
            "kv_host": {**self._kv_pool.stats(),
                        "policy": self._kv_policy.stats()},
            "structured": structured,
        }
        if self.paged:
            used = sum(min(s.kv_written, len(s.tokens))
                       for s in self.slots.slots)
            out["kv_blocks"] = self._kv_blocks.stats(used_tokens=used)
        if self._kv_radix is not None:
            out["kv_radix"] = self._kv_radix.stats()
        return out

    # ---------------- jitted steps ----------------

    def _sink(self, kind: str, **payload) -> None:
        """Publish a device-call replay descriptor to the attached
        multi-host call sink (no-op single-host)."""
        if self.call_sink is not None:
            self.call_sink(kind, payload)

    def _note_compile(self, kind: str, **attrs: Any) -> None:
        """A jitted-executable cache miss while serving traffic is a
        latency incident (the compile stalls the engine thread for
        seconds): record it in the event log. Warmup misses (before
        start()) are the expected cost and are not events — but every
        miss lands in the perf ledger's compile table either way, so
        /perf answers "which shapes compiled, and when"."""
        self._perf.note_compile(kind, serving=self._started, **attrs)
        if self._started:
            self._events.emit("recompile", severity="warning",
                              what=kind, **attrs)

    # Program keys for the perf ledger's per-program device-time
    # attribution: every step record carries the SAME executable key
    # its dispatch's _note_compile would build, so /perf's programs
    # block and compile table join exactly (perf.program_key docs).

    def _decode_program(self, kv_len: int, steps: int,
                        st_on: bool) -> str:
        return program_key(
            "decode", kv_len=kv_len, steps=steps,
            **({"structured": True} if st_on else {}),
            **self._kvq_attrs,
            **({"kv_layout": "paged"} if self.paged else {}))

    def _prefill_program(self, start: int, bucket: int) -> str:
        """The executable key _run_chunk_prefill(start, bucket) routes
        to — the paged ctx computation is duplicated deliberately so
        callers can stamp BEFORE dispatch mutates their state."""
        if self.paged:
            ctx = next((b for b in _KV_BUCKETS
                        if b >= start + bucket and b <= self.max_len),
                       self.max_len)
            return program_key("prefill", chunk=bucket, ctx=ctx,
                               kv_layout="paged", **self._kvq_attrs)
        return program_key("prefill", chunk=bucket, **self._kvq_attrs)

    def _fetch(self, arr) -> Future:
        """Submit a device→host copy on the fetch pool, tracked so
        restart() can wait for every outstanding copy to land before
        rebuilding device state."""
        fut = self._fetch_pool.submit(np.asarray, arr)
        self._fetch_pending.add(fut)
        fut.add_done_callback(self._fetch_pending.discard)
        return fut

    def _put(self, arr):
        """Host array (or PRNG key) → device, replicated over the mesh
        when present."""
        if self.mesh is None:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(arr, NamedSharding(self.mesh, PartitionSpec()))

    def _arg(self, arr):
        """Host array destined to be a jitted-call argument. Without a
        mesh the numpy array is passed as-is — the call's own transfer
        is one dispatch, where an explicit device_put is a separate
        host→device transfer per array. With a mesh, explicit
        replicated placement is required."""
        return arr if self.mesh is None else self._put(arr)

    def _replicate_sharding(self):
        """Fully-replicated NamedSharding on the mesh (None when single
        device): constrains host-fetched program outputs so every host
        of a multi-process (DCN) mesh can read them."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec())

    def _get_decode_fn(self, kv_len: int, steps: int | None = None,
                       with_history: bool = False,
                       with_fsm: bool = False):
        """K decode steps in one jitted call (K = ``steps``, default
        steps_per_call; the dispatcher also compiles the short
        ``steps_burst`` variant for admission-latency-sensitive moments).
        ``with_history`` (auto-spec mode) additionally maintains the
        speculative history buffer so probe calls draft from fresh text.

        The whole per-slot decode state is threaded through the call so
        nothing round-trips to the host between steps: carry = (sliced
        K/V, current token, positions, rng). Returns all K sampled
        tokens; the host consumes them at retirement (SURVEY.md §7 hard
        part #3 — the naive per-step blocking get this replaces
        serialised device and host work).
        """
        steps = self.steps_per_call if steps is None else steps
        sp = self.mesh.shape.get("sp", 1) if self.mesh is not None else 1
        if sp > 1:
            # The sp path attends the FULL sp-sharded cache through
            # decode_attention_sharded (per-chip O(S/sp) folds + a
            # statistics psum — masking bounds the horizon, so KV-
            # bucket specialisation buys nothing); one executable per
            # step count.
            kv_len = self.max_len
        fn = self._decode_fns.get((kv_len, steps, with_history,
                                   with_fsm))
        if fn is not None:
            return fn
        self._note_compile("decode", kv_len=kv_len, steps=steps,
                           **({"structured": True} if with_fsm else {}),
                           **self._kvq_attrs,
                           **({"kv_layout": "paged"} if self.paged
                              else {}))
        # BOTH kernel variants ride the scatter path now
        # (forward_decode routes pallas_dense/pallas_paged), so the
        # kernel composes with everything the scatter family carries:
        # int8 KV, history/spec, structured. The dense kernel needs the
        # bucket divisible by its 128 block — true for the
        # power-of-two >= 512 buckets, false only for a short max_len
        # fallback bucket, which keeps the XLA read.
        use_pallas = self.use_pallas_attention and kv_len % 128 == 0
        scatter = self._scatter_decode
        pallas_paged = self.paged and self.use_pallas_attention
        pallas_dense = use_pallas and not self.paged and scatter
        bsz = self.kv_block_size
        rows = jnp.arange(self.num_slots)
        max_len = self.max_len
        replicate = self._replicate_sharding()
        if with_fsm:
            # Constrained variant (docs/STRUCTURED.md): identical step
            # math plus (1) a per-slot allowed-token mask gathered from
            # the packed-bitmask union table by FSM state and applied
            # to the penalised logits BEFORE candidate preselection —
            # composing with penalties/top-k/top-p exactly like a
            # penalty — and (2) the state advance, a two-gather chain
            # next = nexts[state, cls[sel, token]], all device-
            # resident: no host sync anywhere on the step path.
            # Unconstrained slots ride along in the FREE state (mask
            # all-ones, self-loop). Dispatched only while a constrained
            # slot is running, so plain serving keeps its executables
            # byte-identical. Single-device scatter path only (the
            # engine rejects constrained requests otherwise).
            assert scatter, "structured decode requires the scatter path"
            fn = self._build_fsm_decode(kv_len, steps, with_history,
                                        rows, max_len)
            self._decode_fns[(kv_len, steps, with_history,
                              with_fsm)] = fn
            return fn
        cache_override = None
        if sp > 1:
            from fasttalk_tpu.parallel.ring_attention import \
                decode_attention_sharded

            mesh = self.mesh

            def cache_override(q, ck, cv, positions):  # noqa: F811
                return decode_attention_sharded(q, ck, cv, positions,
                                                mesh)

        if with_history:
            # Auto-spec plain call: identical decode, plus maintaining
            # the spec history invariant (history[s, pos] = fed token)
            # so a later probe/spec call drafts from fresh text.
            assert scatter

            @partial(jax.jit, donate_argnums=(1, 2, 3))
            def decode_call_hist(params, cache: KVCache, history, counts,
                                 cur_tokens, positions, active, temps,
                                 topks, topps, reps, press, freqs, rng,
                                 bt=None):
                def step(carry, _):
                    ck, cv, ks, vs, hist, cnt, cur, pos, key = carry
                    key, sub = jax.random.split(key)
                    act = jnp.logical_and(active, pos < kv_len)
                    wp = jnp.where(act, pos, max_len)
                    hist = hist.at[rows, wp].set(cur, mode="drop",
                                                 unique_indices=True)
                    cnt = cnt.at[rows, cur].add(act.astype(jnp.int32),
                                                unique_indices=True)
                    logits, newc = forward_decode(
                        params, self.cfg, cur, pos,
                        KVCache(ck, cv, ks, vs), act,
                        attn_len=kv_len,
                        pallas_int8=self.use_pallas_int8,
                        pallas_int4=self.use_pallas_int4,
                        block_table=bt, block_size=bsz,
                        pallas_paged=pallas_paged,
                        pallas_dense=pallas_dense)
                    lg = apply_penalties(logits[:, :self.sample_vocab],
                                         cnt, reps, press, freqs)
                    nxt = sample_tokens(lg, sub, temps, topks, topps,
                                        method=self.sampling_method)
                    pos = pos + act.astype(pos.dtype)
                    return (newc.k, newc.v, newc.k_scale, newc.v_scale,
                            hist, cnt, nxt, pos, key), nxt

                (ck, cv, ks, vs, hist, cnt, cur, pos, rng), toks = \
                    jax.lax.scan(
                        step, (cache.k, cache.v, cache.k_scale,
                               cache.v_scale, history, counts,
                               cur_tokens, positions, rng), None,
                        length=steps)
                return KVCache(ck, cv, ks, vs), hist, cnt, toks, cur, \
                    pos, rng

            self._decode_fns[(kv_len, steps, with_history, False)] = \
                decode_call_hist
            return decode_call_hist

        @partial(jax.jit, donate_argnums=(1, 2))
        def decode_call(params, cache: KVCache, counts, cur_tokens,
                        positions, active, temps, topks, topps,
                        reps, press, freqs, rng, bt=None):
            if scatter:
                def step(carry, _):
                    ck, cv, ks, vs, cnt, cur, pos, key = carry
                    key, sub = jax.random.split(key)
                    # A slot that finished mid-pipeline keeps "decoding"
                    # until the host reconciles; clamp it off the
                    # attention horizon so its garbage writes can never
                    # clobber live rows.
                    act = jnp.logical_and(active, pos < kv_len)
                    # Count the token being FED (it was emitted last
                    # step or by prefill), so the penalty at sampling
                    # time covers every emitted token exactly once.
                    cnt = cnt.at[rows, cur].add(act.astype(jnp.int32),
                                                unique_indices=True)
                    logits, newc = forward_decode(
                        params, self.cfg, cur, pos,
                        KVCache(ck, cv, ks, vs), act,
                        attn_len=kv_len,
                        pallas_int8=self.use_pallas_int8,
                        pallas_int4=self.use_pallas_int4,
                        block_table=bt, block_size=bsz,
                        pallas_paged=pallas_paged,
                        pallas_dense=pallas_dense)
                    lg = apply_penalties(logits[:, :self.sample_vocab],
                                         cnt, reps, press, freqs)
                    nxt = sample_tokens(lg, sub, temps, topks, topps,
                                        method=self.sampling_method)
                    pos = pos + act.astype(pos.dtype)
                    return (newc.k, newc.v, newc.k_scale, newc.v_scale,
                            cnt, nxt, pos, key), nxt

                (ck, cv, ks, vs, cnt, cur, pos, rng), toks = \
                    jax.lax.scan(
                        step, (cache.k, cache.v, cache.k_scale,
                               cache.v_scale, counts, cur_tokens,
                               positions, rng), None, length=steps)
                return KVCache(ck, cv, ks, vs), cnt, toks, cur, pos, rng

            ck = jax.lax.slice_in_dim(cache.k, 0, kv_len, axis=2)
            cv = jax.lax.slice_in_dim(cache.v, 0, kv_len, axis=2)

            def step(carry, _):
                sk, sv, cnt, cur, pos, key = carry
                key, sub = jax.random.split(key)
                act = jnp.logical_and(active, pos < kv_len)
                cnt = cnt.at[rows, cur].add(act.astype(jnp.int32),
                                            unique_indices=True)
                logits, small = forward(
                    params, self.cfg, cur[:, None], pos[:, None],
                    KVCache(sk, sv), pos, write_mask=act,
                    pallas_decode=use_pallas,
                    pallas_int8=self.use_pallas_int8,
                    pallas_int4=self.use_pallas_int4,
                    cache_attn_override=cache_override)
                lg = apply_penalties(logits[:, -1, :self.sample_vocab],
                                     cnt, reps, press, freqs)
                nxt = sample_tokens(lg, sub, temps, topks, topps,
                                    method=self.sampling_method)
                pos = pos + act.astype(pos.dtype)
                return (small.k, small.v, cnt, nxt, pos, key), nxt

            (ck, cv, cnt, cur, pos, rng), toks = jax.lax.scan(
                step, (ck, cv, counts, cur_tokens, positions, rng), None,
                length=steps)
            new_k = jax.lax.dynamic_update_slice_in_dim(
                cache.k, ck, 0, axis=2)
            new_v = jax.lax.dynamic_update_slice_in_dim(
                cache.v, cv, 0, axis=2)
            # Sampled tokens leave the program fully replicated: on a
            # multi-host (DCN) mesh a host can only fetch an array whose
            # addressable shards cover it — and [K, S] ints are nothing
            # next to the batch all-reduces GSPMD already inserted.
            if replicate is not None:
                toks = jax.lax.with_sharding_constraint(toks, replicate)
            return KVCache(new_k, new_v), cnt, toks, cur, pos, rng

        self._decode_fns[(kv_len, steps, with_history, False)] = \
            decode_call
        return decode_call

    def _build_fsm_decode(self, kv_len: int, steps: int,
                          with_history: bool, rows, max_len: int):
        """The constrained K-step decode programs (see _get_decode_fn).
        Carry gains the per-slot FSM state; the union tables ride as
        ordinary (non-donated) arguments, so arena growth re-uploads
        without recompiling, and the executables key only on the
        bucketed table shapes.

        DELIBERATE duplication of _get_decode_fn's scatter step bodies
        (KEEP THEM IN SYNC — any change to count/forward/penalty/
        sample there must land here too): the unconstrained variants'
        byte-identical-executable guarantee is an acceptance-tested
        contract, and sharing closures would put every future fsm-side
        edit one trace-time branch away from perturbing it."""
        sv = self.sample_vocab
        bsz = self.kv_block_size
        pallas_paged = self.paged and self.use_pallas_attention
        pallas_dense = (self.use_pallas_attention and not self.paged
                        and kv_len % 128 == 0)
        powers = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)

        def masked(lg, fst, masks):
            bits = masks[fst]                        # [S, W] gather
            # Unpack by broadcast-test-reshape (cheaper than a [S, sv]
            # word gather: no per-element index math, and XLA fuses
            # the bit test straight into the select).
            allow = (bits[:, :, None]
                     & powers[None, None, :]) != 0   # [S, W, 32]
            allow = allow.reshape(bits.shape[0], -1)[:, :sv]
            return jnp.where(allow, lg, jnp.float32(-1e30))

        def advance(fst, nxt, act, sel, cls, nexts):
            ns = nexts[fst, cls[sel, nxt]]
            return jnp.where(act, ns, fst)

        if with_history:
            @partial(jax.jit, donate_argnums=(1, 2, 3, 4))
            def decode_fsm_hist(params, cache: KVCache, history, counts,
                                fsm_state, cur_tokens, positions,
                                active, temps, topks, topps, reps,
                                press, freqs, rng, sel, masks, cls,
                                nexts, bt=None):
                def step(carry, _):
                    ck, cv, ks, vs, hist, cnt, fst, cur, pos, key = carry
                    key, sub = jax.random.split(key)
                    act = jnp.logical_and(active, pos < kv_len)
                    wp = jnp.where(act, pos, max_len)
                    hist = hist.at[rows, wp].set(cur, mode="drop",
                                                 unique_indices=True)
                    cnt = cnt.at[rows, cur].add(act.astype(jnp.int32),
                                                unique_indices=True)
                    logits, newc = forward_decode(
                        params, self.cfg, cur, pos,
                        KVCache(ck, cv, ks, vs), act,
                        attn_len=kv_len,
                        pallas_int8=self.use_pallas_int8,
                        pallas_int4=self.use_pallas_int4,
                        block_table=bt, block_size=bsz,
                        pallas_paged=pallas_paged,
                        pallas_dense=pallas_dense)
                    lg = apply_penalties(logits[:, :sv], cnt, reps,
                                         press, freqs)
                    lg = masked(lg, fst, masks)
                    nxt = sample_tokens(lg, sub, temps, topks, topps,
                                        method=self.sampling_method)
                    fst = advance(fst, nxt, act, sel, cls, nexts)
                    pos = pos + act.astype(pos.dtype)
                    return (newc.k, newc.v, newc.k_scale, newc.v_scale,
                            hist, cnt, fst, nxt, pos, key), nxt

                (ck, cv, ks, vs, hist, cnt, fst, cur, pos, rng), toks \
                    = jax.lax.scan(
                        step, (cache.k, cache.v, cache.k_scale,
                               cache.v_scale, history, counts,
                               fsm_state, cur_tokens, positions, rng),
                        None, length=steps)
                return KVCache(ck, cv, ks, vs), hist, cnt, fst, toks, \
                    cur, pos, rng

            return decode_fsm_hist

        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def decode_fsm(params, cache: KVCache, counts, fsm_state,
                       cur_tokens, positions, active, temps, topks,
                       topps, reps, press, freqs, rng, sel, masks, cls,
                       nexts, bt=None):
            def step(carry, _):
                ck, cv, ks, vs, cnt, fst, cur, pos, key = carry
                key, sub = jax.random.split(key)
                act = jnp.logical_and(active, pos < kv_len)
                cnt = cnt.at[rows, cur].add(act.astype(jnp.int32),
                                            unique_indices=True)
                logits, newc = forward_decode(
                    params, self.cfg, cur, pos,
                    KVCache(ck, cv, ks, vs), act,
                    attn_len=kv_len,
                    pallas_int8=self.use_pallas_int8,
                    pallas_int4=self.use_pallas_int4,
                    block_table=bt, block_size=bsz,
                    pallas_paged=pallas_paged,
                    pallas_dense=pallas_dense)
                lg = apply_penalties(logits[:, :sv], cnt, reps,
                                     press, freqs)
                lg = masked(lg, fst, masks)
                nxt = sample_tokens(lg, sub, temps, topks, topps,
                                    method=self.sampling_method)
                fst = advance(fst, nxt, act, sel, cls, nexts)
                pos = pos + act.astype(pos.dtype)
                return (newc.k, newc.v, newc.k_scale, newc.v_scale,
                        cnt, fst, nxt, pos, key), nxt

            (ck, cv, ks, vs, cnt, fst, cur, pos, rng), toks = \
                jax.lax.scan(
                    step, (cache.k, cache.v, cache.k_scale,
                           cache.v_scale, counts, fsm_state,
                           cur_tokens, positions, rng), None,
                    length=steps)
            return KVCache(ck, cv, ks, vs), cnt, fst, toks, cur, pos, \
                rng

        return decode_fsm

    def _get_spec_decode_fn(self, kv_len: int, steps: int):
        """K speculative steps in one jitted call (single-device scatter
        path). Each step, entirely on device:

        1. maintain the history invariant ``history[s, pos] = cur``;
        2. DRAFT via prompt-lookup: find the most recent prior
           occurrence of the current token in the slot's history and
           propose the G tokens that followed it;
        3. VERIFY current + draft (T = G+1 positions) in one
           ``forward_decode_multi`` block — same weight-streaming cost
           as ~one plain step at small batch, since decode is
           weight-bound;
        4. ACCEPT: sample every position; keep the longest prefix where
           the sample equals the draft; emit accepted+1 tokens (the
           first mismatch IS the residual-distribution sample, so the
           output distribution is exactly the plain-decode one);
        5. append the emitted tokens to the history, advance positions
           by n_out.

        Rejected positions' KV is garbage but unreachable: attention
        masks to each query's absolute position, and the next block's
        writes start at the accepted length, overwriting it first.
        Returns per-step (tokens [K, S, T], n_out [K, S]); the host
        consumes the first n_out tokens per row.
        """
        key = (kv_len, steps)
        fn = self._spec_fns.get(key)
        if fn is not None:
            return fn
        self._note_compile("spec_decode", kv_len=kv_len, steps=steps)
        from fasttalk_tpu.models.llama import forward_decode_multi

        G = self.spec_draft
        T = G + 1
        S = self.num_slots
        max_len = self.max_len
        sv = self.sample_vocab

        bsz = self.kv_block_size
        # The verify block (T = G+1 positions) runs through the
        # multi-token q generalisation of the Pallas kernels — the
        # same gates as the plain decode families (_get_decode_fn).
        pallas_paged = self.paged and self.use_pallas_attention
        pallas_dense = (self.use_pallas_attention and not self.paged
                        and kv_len % 128 == 0)

        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def spec_call(params, cache: KVCache, history, counts, cur_tokens,
                      positions, active, temps, topks, topps,
                      reps, press, freqs, rng, bt=None):
            rows = jnp.arange(S)

            def step(carry, _):
                ck, cv, hist, cnt, cur, pos, key = carry
                # Need T columns of cache headroom inside this bucket;
                # slots without it sit the step out (the dispatcher
                # falls back to plain decode before this can starve a
                # request — see _dispatch_decode).
                act = jnp.logical_and(active, pos + T <= kv_len)
                wp = jnp.where(act, pos, max_len)
                hist = hist.at[rows, wp].set(cur, mode="drop",
                                             unique_indices=True)
                # Penalty base counts: the fed token (emitted last
                # block) counts now, same as the plain decode step.
                cnt = cnt.at[rows, cur].add(act.astype(jnp.int32),
                                            unique_indices=True)
                idx = jnp.arange(max_len)
                m = jnp.logical_and(hist == cur[:, None],
                                    idx[None, :] < pos[:, None])
                j = jnp.max(jnp.where(m, idx[None, :], -1), axis=1)
                start = jnp.clip(j + 1, 0, max_len - 1)
                didx = jnp.clip(start[:, None] + jnp.arange(G)[None, :],
                                0, max_len - 1)
                drafts = jnp.take_along_axis(hist, didx, axis=1)  # [S, G]
                tokens_in = jnp.concatenate([cur[:, None], drafts], 1)
                logits, newc = forward_decode_multi(
                    params, self.cfg, tokens_in, pos, KVCache(ck, cv),
                    act, attn_len=kv_len,
                    pallas_int8=self.use_pallas_int8,
                    pallas_int4=self.use_pallas_int4,
                    block_table=bt, block_size=bsz,
                    pallas_paged=pallas_paged,
                    pallas_dense=pallas_dense)
                key, sub = jax.random.split(key)
                # EXACT per-position penalty counts, without vocab-wide
                # per-position intermediates: block position j is
                # conditioned on fed tokens cur, d_1..d_j — if position
                # j's sample is ever emitted, those drafts were accepted
                # (= emitted), so plain decode would have counted them.
                # Only the <= G draft-token columns can differ from the
                # base counts, so penalise everything against the base
                # [S, 1, V] (broadcast, fused by XLA), then re-penalise
                # just those entries with their within-block counts and
                # scatter them in. Keeps speculative decoding exactly
                # distribution-preserving under penalties.
                lgf = logits[..., :sv].astype(jnp.float32)  # [S, T, sv]
                r3 = reps[:, None, None]
                p3 = press[:, None, None]
                f3 = freqs[:, None, None]
                lg = penalize_values(
                    lgf, cnt[:, None, :].astype(jnp.float32), r3, p3, f3)
                # occ[s, i, k]: occurrences of d_i among d_1..d_{k+1};
                # extra count of token d_i at block position j is its
                # occurrence count among the fed d_1..d_j.
                eq = (drafts[:, :, None] == drafts[:, None, :]) \
                    .astype(jnp.float32)                      # [S, G, G]
                extra = jnp.concatenate(
                    [jnp.zeros((S, G, 1), jnp.float32),
                     jnp.cumsum(eq, axis=2)], axis=2)         # [S, G, T]
                dcl = jnp.minimum(drafts, sv - 1)
                dcol = jnp.broadcast_to(dcl[:, None, :], (S, T, G))
                raw = jnp.take_along_axis(lgf, dcol, axis=2)  # [S, T, G]
                base_c = jnp.take_along_axis(cnt, dcl, axis=1) \
                    .astype(jnp.float32)                      # [S, G]
                c_true = base_c[:, None, :] \
                    + jnp.swapaxes(extra, 1, 2)               # [S, T, G]
                corr = penalize_values(raw, c_true, r3, p3, f3)
                # Equal drafts get equal corrected values, so the
                # duplicate-index scatter is value-consistent;
                # out-of-vocab draft ids (prompt tokens beyond the
                # tokenizer vocab) drop — they can never be sampled.
                scat = jnp.where(
                    jnp.broadcast_to((drafts < sv)[:, None, :],
                                     (S, T, G)), dcol, sv)
                lg = lg.at[jnp.arange(S)[:, None, None],
                           jnp.arange(T)[None, :, None],
                           scat].set(corr, mode="drop")
                t_samp = sample_tokens(
                    lg.reshape(S * T, sv), sub, jnp.repeat(temps, T),
                    jnp.repeat(topks, T), jnp.repeat(topps, T),
                    method=self.sampling_method).reshape(S, T)
                match = (t_samp[:, :-1] == drafts).astype(jnp.int32)
                a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # 0..G
                n_out = jnp.where(act, a + 1, 0)
                new_cur = jnp.where(
                    act, jnp.take_along_axis(t_samp, a[:, None], 1)[:, 0],
                    cur)
                out_idx = pos[:, None] + 1 + jnp.arange(T)[None, :]
                keep = jnp.arange(T)[None, :] < n_out[:, None]
                hist = hist.at[
                    rows[:, None], jnp.where(keep, out_idx, max_len)].set(
                    t_samp, mode="drop")
                # Commit accepted drafts to the counts (they were fed
                # AND emitted). The residual sample t_samp[:, a] is
                # new_cur — counted when fed next block, like plain
                # decode's sampled token.
                add = jnp.arange(T)[None, :] < (n_out - 1)[:, None]
                cnt = cnt.at[rows[:, None],
                             jnp.where(add, t_samp, sv)].add(
                    jnp.int32(1), mode="drop")
                pos = pos + n_out
                # n_out packed as a trailing column: ONE host fetch per
                # call instead of a tuple's two.
                packed = jnp.concatenate([t_samp, n_out[:, None]], axis=1)
                return (newc.k, newc.v, hist, cnt, new_cur, pos, key), \
                    packed

            (ck, cv, hist, cnt, cur, pos, rng), toks = jax.lax.scan(
                step, (cache.k, cache.v, history, counts, cur_tokens,
                       positions, rng), None, length=steps)
            return (KVCache(ck, cv), hist, cnt, toks, cur, pos, rng)

        self._spec_fns[key] = spec_call
        return spec_call

    # Dense-stamp alignment: shares round down to this granule (the
    # same minimum the slot scan uses), not to a power of two — the r4
    # pow2 bucketing (_share_granule) wasted up to HALF of a matched
    # prefix on the stamp path. The executable family stays bounded at
    # one per pow2 chunk length because _stamp_prefix decomposes the
    # share into descending pow2 chunks over an offset-parameterized
    # copy (the offset is a traced operand, not part of the jit key).
    _STAMP_GRANULE = 16

    @classmethod
    def _stamp_chunks(cls, share: int) -> list[tuple[int, int]]:
        """(offset, length) power-of-two chunks exactly covering
        ``share`` rounded down to the stamp granule. At most
        log2(max_len) chunks, each >= the granule."""
        share -= share % cls._STAMP_GRANULE
        out: list[tuple[int, int]] = []
        off = 0
        while off < share:
            rem = share - off
            chunk = 1 << (rem.bit_length() - 1)
            out.append((off, chunk))
            off += chunk
        return out

    def _stamp_prefix(self, src: int, dst: int, share: int) -> int:
        """Dense shared-prefix stamp: copy the source slot's leading
        rows onto ``dst`` in pow2 chunks (granule-aligned, so at most
        granule-1 matched tokens are wasted instead of up to half
        under the old pow2 round-down). Returns rows stamped."""
        done = 0
        for off, ln in self._stamp_chunks(share):
            self._sink("prefix_copy", share=ln, off=off, src=src,
                       dst=dst)
            self.cache = self._get_prefix_copy_fn(ln)(
                self.cache, np.int32(src), np.int32(dst),
                np.int32(off))
            done = off + ln
        return done

    def _get_prefix_copy_fn(self, plen: int):
        """Copy one slot's KV rows [off, off+plen) onto another slot —
        one chunk of the shared-prefix stamp. Pure HBM traffic
        (2·L·plen·Kv·H elements), ordered against prefills and decode
        calls by the donated-cache chain like every other cache op.
        The row offset is a traced operand: one executable serves
        every chunk position, keeping the family at one entry per
        pow2 chunk length."""
        key = ("pcopy", plen)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        shape = (self.cfg.num_layers, 1, plen, self.cfg.num_kv_heads,
                 self.cfg.head_dim)
        # Quantized tier: the stamp copies int8 rows + their scale rows
        # — half the HBM traffic of the bf16 stamp, same ordering
        # guarantees (donated-cache chain).
        kvq = self.kv_quant
        sshape = (self.cfg.num_layers, 1, plen, self.kv_scale_granule)

        @partial(jax.jit, donate_argnums=(0,))
        def prefix_copy(cache: KVCache, src, dst, off):
            rk = jax.lax.dynamic_slice(cache.k, (0, src, off, 0, 0),
                                       shape)
            rv = jax.lax.dynamic_slice(cache.v, (0, src, off, 0, 0),
                                       shape)
            new_k = jax.lax.dynamic_update_slice(cache.k, rk,
                                                 (0, dst, off, 0, 0))
            new_v = jax.lax.dynamic_update_slice(cache.v, rv,
                                                 (0, dst, off, 0, 0))
            if not kvq:
                return KVCache(new_k, new_v)
            rks = jax.lax.dynamic_slice(cache.k_scale,
                                        (0, src, off, 0), sshape)
            rvs = jax.lax.dynamic_slice(cache.v_scale,
                                        (0, src, off, 0), sshape)
            return KVCache(
                new_k, new_v,
                jax.lax.dynamic_update_slice(cache.k_scale, rks,
                                             (0, dst, off, 0)),
                jax.lax.dynamic_update_slice(cache.v_scale, rvs,
                                             (0, dst, off, 0)))

        self._prefill_fns[key] = prefix_copy
        return prefix_copy

    # ---------------- session KV host-offload tier ----------------
    # (kvcache/: hostpool + offload + policy; docs/KVCACHE.md)

    def _get_kv_slice_fn(self, bucket: int):
        """Read one slot's leading ``bucket`` KV rows (no donation —
        the cache chain is untouched; see kvcache/offload.py)."""
        key = ("kvslice", bucket)
        fn = self._prefill_fns.get(key)
        if fn is None:
            self._note_compile("kv_offload", bucket=bucket,
                               **self._kvq_attrs)
            fn = make_kv_slice_fn(self.cfg, bucket,
                                  self.kv_scale_granule)
            self._prefill_fns[key] = fn
        return fn

    def _get_kv_restore_fn(self, bucket: int):
        """Write parked rows back into a slot (donated cache — chains
        with prefill/decode like every other cache op)."""
        key = ("kvrestore", bucket)
        fn = self._prefill_fns.get(key)
        if fn is None:
            self._note_compile("kv_restore", bucket=bucket,
                               **self._kvq_attrs)
            fn = make_kv_restore_fn(self.cfg, bucket, KVCache,
                                    self.kv_scale_granule)
            self._prefill_fns[key] = fn
        return fn

    def _get_paged_kv_slice_fn(self, bucket: int):
        key = ("pkvslice", bucket)
        fn = self._prefill_fns.get(key)
        if fn is None:
            self._note_compile("kv_offload", bucket=bucket,
                               kv_layout="paged", **self._kvq_attrs)
            fn = make_paged_kv_slice_fn(self.cfg, bucket,
                                        self.kv_scale_granule)
            self._prefill_fns[key] = fn
        return fn

    def _get_paged_kv_restore_fn(self, bucket: int):
        key = ("pkvrestore", bucket)
        fn = self._prefill_fns.get(key)
        if fn is None:
            self._note_compile("kv_restore", bucket=bucket,
                               kv_layout="paged", **self._kvq_attrs)
            fn = make_paged_kv_restore_fn(self.cfg, bucket, KVCache,
                                          self.kv_scale_granule)
            self._prefill_fns[key] = fn
        return fn

    def _park_on_evict(self, victim: Slot) -> None:
        """SlotManager eviction hook (engine thread, inside acquire):
        snapshot the victim's kept KV rows to the host pool before the
        slot is cleared for its new session. The slice program is
        dispatched here (ordered before the new occupant's prefill by
        dispatch order); the blocking device→host fetch runs on the
        offload thread, so admission never waits on the copy."""
        if not self._kv_pool.enabled:
            return
        kept = min(victim.kv_written, len(victim.tokens))
        if kept < self._kv_policy.min_tokens:
            return
        if self._kv_pool.parked_len(victim.session_id) >= kept \
                or self._kv_offload.parking(victim.session_id):
            return  # an up-to-date snapshot is parked or in flight
        self._park_slot(victim, kept)

    def _park_slot(self, slot: Slot, kept: int) -> None:
        bucket = kv_bucket(kept, self.max_len)
        t0 = time.monotonic()
        trim = None
        if self.paged:
            # Paged tier: gather the slot's BLOCK LIST (flat pool rows
            # via its table) rather than a dense slot slice, and trim
            # the host entry to exact per-block bytes — the pool
            # budget accounts blocks, not power-of-two padding.
            bucket = max(bucket, self.kv_block_size)
            trim = (blocks_for(kept, self.kv_block_size)
                    * self.kv_block_size)
            out = self._get_paged_kv_slice_fn(bucket)(
                self.cache,
                self._arg(self._paged_read_indices(slot.index,
                                                   bucket)))
        else:
            out = self._get_kv_slice_fn(bucket)(
                self.cache, np.int32(slot.index))
        if self._tracer.enabled:
            # Device-time attribution row for the park slice (no token
            # stats — engine_op records feed only the busy union and
            # the per-program ledger).
            self._tracer.step(
                "engine_op", t0, time.monotonic(), kind="kv_offload",
                program=program_key(
                    "kv_offload", bucket=bucket, **self._kvq_attrs,
                    **({"kv_layout": "paged"} if self.paged else {})))
        # Quantized tier: the slice carries int8 rows + scale rows;
        # the pool entry's nbytes (and therefore the budget, the
        # kv_host_bytes gauge and the copy-bandwidth EMA) see the
        # honest quantized footprint.
        scales = (out[2], out[3]) if self.kv_quant else None
        self._kv_offload.park(slot.session_id, list(slot.tokens[:kept]),
                              kept, bucket, out[0], out[1], t0,
                              scales=scales, trim_rows=trim)

    def _prefill_park_finish(self, req: _Request, slot: Slot) -> None:
        """Terminal step of a ``prefill_only`` request (disaggregated
        prefill tier, router/disagg.py): snapshot the freshly written
        prompt KV to the host pool and finish with reason
        ``prefill_parked`` — no first-token sample, no activation, the
        slot frees immediately. The park's D2H fetch runs on the
        offload copy thread; the router polls ``parked_kv_info`` until
        the entry lands before migrating it out. Engine thread only."""
        kept = min(slot.kv_written, len(slot.tokens))
        if kept >= 1 and self._kv_pool.enabled \
                and self._kv_pool.parked_len(req.session_id) < kept \
                and not self._kv_offload.parking(req.session_id):
            self._park_slot(slot, kept)
        self._finish(req, "prefill_parked")

    def _try_restore(self, req: _Request, slot: Slot,
                     prompt: list[int]) -> int:
        """Restore a returning session's kept prefix from the host pool
        into its freshly acquired slot. Returns the number of leading
        prompt tokens now resident (0 = no entry / policy chose
        prefill; the caller falls through to shared-prefix/full
        prefill). Engine thread only."""
        if not self._kv_pool.enabled:
            return 0
        entry = self._kv_pool.get(req.session_id)
        if entry is None:
            self._kv_pool.note_lookup(False)
            return 0
        # Same trust rules as slot-resident reuse: at least one prompt
        # token must run through the model, and only the matched prefix
        # is believable KV.
        match = _lcp(entry.tokens, prompt,
                     min(entry.kept, len(prompt) - 1))
        if not self._kv_policy.should_restore(match, entry.nbytes):
            self._kv_pool.note_lookup(False)
            return 0  # entry stays parked for a later, longer match
        if self.kv_quant and entry.k_scale is None:
            # A bf16-era entry cannot restore into the quantized cache
            # (unreachable within one engine lifetime — the pool is
            # engine-owned — but never corrupt KV over an assumption).
            self._kv_pool.note_lookup(False)
            return 0
        if self.paged and not self._kv_blocks.ensure(slot.index, match):
            # No blocks for the restored prefix: leave the entry
            # parked; the full-prefill fallback faces the admission
            # check next.
            self._kv_pool.note_lookup(False)
            return 0
        t0 = time.monotonic()
        try:
            if _fp.enabled:
                _fp.fire("kv.restore.dispatch",
                         request_id=req.request_id,
                         session_id=req.session_id)
            paged = self.paged
            if paged:
                fn = self._get_paged_kv_restore_fn(entry.bucket)
                # Scatter target: the freshly allocated block list
                # (positions past it carry distinct OOR indices and
                # drop — a restore allocates exactly
                # ceil(match/block_size) blocks).
                tgt = (self._arg(self._paged_write_indices(
                    slot.index, 0, entry.bucket)),)
            else:
                fn = self._get_kv_restore_fn(entry.bucket)
                tgt = (np.int32(slot.index),)
            k_arg, v_arg = entry.k_dev, entry.v_dev
            prestaged = k_arg is not None and v_arg is not None
            if not prestaged:  # prestage didn't land
                if paged:  # stored rows are block-trimmed: pad back
                    k_arg = self._arg(pad_rows(entry.k, entry.bucket))
                    v_arg = self._arg(pad_rows(entry.v, entry.bucket))
                else:
                    k_arg, v_arg = self._arg(entry.k), self._arg(entry.v)
            if self.kv_quant:
                # Scales ride with their rows (prestaged before
                # k_dev/v_dev on the copy thread, so prestaged rows
                # imply staged scales).
                ks_arg, vs_arg = entry.k_scale_dev, entry.v_scale_dev
                if not prestaged or ks_arg is None or vs_arg is None:
                    ks_arg = self._arg(pad_rows(entry.k_scale,
                                                entry.bucket)
                                       if paged else entry.k_scale)
                    vs_arg = self._arg(pad_rows(entry.v_scale,
                                                entry.bucket)
                                       if paged else entry.v_scale)
                self.cache = fn(self.cache, k_arg, v_arg, ks_arg,
                                vs_arg, *tgt)
            else:
                self.cache = fn(self.cache, k_arg, v_arg, *tgt)
        except Exception as e:
            # A failed restore dispatch must degrade to a full
            # prefill, never crash the engine thread mid-admission —
            # UNLESS the restore program already CONSUMED the donated
            # cache: serving on would use-after-free the dead buffer
            # at the next dispatch, a delayed and misattributed
            # crash. Re-raise into the engine crash path instead
            # (_abort_all + supervised restart rebuild the cache).
            if self.cache is None or getattr(
                    self.cache.k, "is_deleted", lambda: False)():
                log.critical(f"kv restore for {req.session_id} "
                             "consumed the donated cache before "
                             f"failing ({e}); escalating to restart")
                raise
            # The entry is purged — after a failed H2D its host copy
            # is suspect, and the byte accounting must stay exact
            # (purge removes exactly entry.nbytes).
            log.error(f"kv restore failed for {req.session_id}: {e}; "
                      "falling back to full prefill")
            if self.paged:
                # Release the blocks ensure() allocated for the failed
                # scatter: the slot's table must be EMPTY again, or
                # the shared-prefix alias stamp (which requires a
                # fresh table) corrupts refcounts on this admission.
                self._kv_blocks.truncate(slot.index, slot.kv_written)
            self._kv_pool.purge(req.session_id)
            self._kv_pool.note_lookup(False)
            return 0
        dt = time.monotonic() - t0
        slot.tokens = list(entry.tokens[:match])
        slot.kv_written = match
        if entry.imported:
            # Migrated-in prefix (disagg handoff / fleet migration):
            # donate the restored blocks to the radix tree NOW, while
            # this slot's table pins them — the decode tier's prefix
            # cache learns handed-off prefills at first use instead of
            # waiting for this stream to finish. Holds are exact: the
            # tree takes allocator holds through the same insert path
            # as every other donation.
            self._radix_insert_slot(slot)
        # Consumed: the KV is device-resident again; a later eviction
        # re-parks the (longer) history.
        self._kv_pool.take(req.session_id)
        self._kv_pool.note_lookup(True)
        self._kv_offload.note_restore(dt)
        if self._tracer.enabled:
            self._tracer.add_span(req.request_id, "kv_restore", t0,
                                  time.monotonic(), tokens=match,
                                  bytes=entry.nbytes,
                                  prestaged=prestaged)
            self._tracer.step(
                "engine_op", t0, time.monotonic(), kind="kv_restore",
                program=program_key(
                    "kv_restore", bucket=entry.bucket,
                    **self._kvq_attrs,
                    **({"kv_layout": "paged"} if paged else {})))
        return match

    def _kv_wait_discount(self, session_id: str,
                          prompt: list[int]) -> float:
        """Expected seconds a host-KV restore shaves off this request's
        service time (0 without a matching parked entry) — consulted by
        the scheduler's estimated-wait shed decision at submit time
        (asyncio side; entry token lists are immutable, so the LCP runs
        safely outside the pool lock)."""
        if not self._kv_pool.enabled:
            return 0.0
        entry = self._kv_pool.get(session_id)
        if entry is None:
            return 0.0
        match = _lcp(entry.tokens, prompt,
                     min(entry.kept, len(prompt) - 1))
        return self._kv_policy.restore_saving_s(match, entry.nbytes)

    def _kv_tick(self) -> None:
        """Once-a-second housekeeping on the engine loop: TTL-sweep the
        pool and park sessions idle past KV_PARK_IDLE_S. Idle parks
        keep the slot pinned — the resident KV still serves the fast
        path; the host copy is insurance, making a later eviction free
        and the history restorable across engine.restart()."""
        if not self._kv_pool.enabled:
            return
        now = time.monotonic()
        if now - self._kv_last_tick < 1.0:
            return
        self._kv_last_tick = now
        self._kv_pool.sweep(now)
        if self._kv_park_idle_s <= 0:
            return
        for slot in self.slots.slots:
            if slot.session_id is None or slot.active:
                continue
            kept = min(slot.kv_written, len(slot.tokens))
            if kept < self._kv_policy.min_tokens \
                    or now - slot.last_used < self._kv_park_idle_s:
                continue
            if self._kv_pool.parked_len(slot.session_id) >= kept \
                    or self._kv_offload.parking(slot.session_id):
                continue  # snapshot current or in flight
            self._park_slot(slot, kept)

    # ---- fleet fabric: cross-replica KV migration (docs/ROUTER.md).
    # All four run off the engine thread (router migrate worker /
    # serving handlers) and touch ONLY the thread-safe host pool — so
    # they keep working on a replica whose engine thread has died,
    # which is exactly when failover migration needs them.

    def export_parked_kv(self, session_id: str):
        entry = self._kv_pool.get(session_id)
        return None if entry is None else strip_device(entry)

    def parked_kv_info(self, session_id: str) -> tuple[int, int] | None:
        entry = self._kv_pool.get(session_id)
        return None if entry is None else (entry.kept, entry.nbytes)

    def drop_parked_kv(self, session_id: str) -> bool:
        return self._kv_pool.purge(session_id)

    def import_parked_kv(self, entry) -> bool:
        """Adopt a migrated entry: validate it against THIS engine's
        cache geometry (a mixed-tier fleet must refuse, never restore
        garbage), normalise the stored rows to this engine's layout
        (paged targets trim to exact block bytes, dense targets pad
        back to the power-of-two bucket), then insert. The put is
        atomic — a refusal at any step leaves the pool untouched."""
        from dataclasses import replace

        if not self._kv_pool.enabled:
            return False
        problem = entry_problem(entry)
        if problem is not None:
            log.warning(f"refused migrated KV for {entry.session_id}: "
                        f"{problem}")
            return False
        L, _, Kv, H = entry.k.shape
        if (L, Kv, H) != (self.cfg.num_layers, self.cfg.num_kv_heads,
                          self.cfg.head_dim):
            log.warning(
                f"refused migrated KV for {entry.session_id}: geometry "
                f"[{L},{Kv},{H}] != engine "
                f"[{self.cfg.num_layers},{self.cfg.num_kv_heads},"
                f"{self.cfg.head_dim}]")
            return False
        if entry.kept > self.max_len:
            log.warning(f"refused migrated KV for {entry.session_id}: "
                        f"kept {entry.kept} exceeds max_len "
                        f"{self.max_len}")
            return False
        if self.kv_quant:
            if entry.k_scale is None or entry.k.dtype != np.int8 \
                    or entry.k_scale.shape[2] != self.kv_scale_granule:
                log.warning(f"refused migrated KV for "
                            f"{entry.session_id}: not int8 rows with "
                            f"granule {self.kv_scale_granule} scales")
                return False
        elif entry.k_scale is not None or entry.k.dtype == np.int8:
            log.warning(f"refused migrated KV for {entry.session_id}: "
                        "quantized entry into a bf16-tier cache")
            return False
        elif entry.k.dtype != jnp.dtype(self.dtype):
            # dtype is part of the tier: a float32 entry in a bf16
            # cache passes every shape check but fails inside the
            # jitted restore program — refuse at import, not at
            # restore time.
            log.warning(f"refused migrated KV for {entry.session_id}: "
                        f"row dtype {entry.k.dtype} != engine cache "
                        f"dtype {jnp.dtype(self.dtype)}")
            return False
        bucket = kv_bucket(entry.kept, self.max_len)
        rows = bucket
        if self.paged:
            bucket = max(bucket, self.kv_block_size)
            rows = (blocks_for(entry.kept, self.kv_block_size)
                    * self.kv_block_size)

        def fit(arr):
            if arr is None:
                return None
            if arr.shape[1] > rows:
                return np.ascontiguousarray(arr[:, :rows])
            return pad_rows(arr, rows)

        k, v = fit(entry.k), fit(entry.v)
        ks, vs = fit(entry.k_scale), fit(entry.v_scale)
        nbytes = int(k.nbytes) + int(v.nbytes)
        if ks is not None:
            nbytes += int(ks.nbytes) + int(vs.nbytes)
        entry = replace(strip_device(entry), k=k, v=v, k_scale=ks,
                        v_scale=vs, bucket=bucket, nbytes=nbytes,
                        tokens=list(entry.tokens),
                        parked_at=time.monotonic(),
                        last_used=time.monotonic(), imported=True)
        # The session may have been released here before (tombstoned):
        # it is coming BACK via migration, so it may return — but the
        # tombstone falls only with a successful insert (a refused
        # import must keep guarding against stale in-flight parks).
        ok = self._kv_pool.put(entry, revive=True)
        if ok:
            # The imported session's next request is typically already
            # on the wire (disagg handoff: the decode stream admits
            # right behind the transfer) — stage the rows to the
            # device now so its restore dispatches H2D-free.
            self._kv_offload.prestage(entry.session_id)
        return ok

    # ---------------- paged KV tier ----------------
    # (KV_LAYOUT=paged — kvcache/blocks.py; docs/KVCACHE.md "Paged
    # tier". All methods engine-thread only unless noted.)

    def _on_slot_unpin(self, slot: Slot) -> None:
        """SlotManager unpin hook: a session leaving its slot (evict
        or release) drops its whole block table — aliased blocks
        survive through their other referents' refcounts. With the
        radix cache on, the departing session's clean prefix blocks
        are donated to the tree FIRST (holds taken before the table
        refs drop), so the next request inherits them instead of
        re-prefilling."""
        if self.paged:
            self._radix_insert_slot(slot)
            self._kv_blocks.release(slot.index)

    def _paged_table_np(self, nb: int) -> np.ndarray:
        """[S, nb] block-table argument for a decode call at KV bucket
        nb * block_size. Unallocated entries stay 0 — their rows sit
        beyond every slot's position mask."""
        tbl = np.zeros((self.num_slots, nb), np.int32)
        for s in range(self.num_slots):
            t = self._kv_blocks.table(s)
            n = min(len(t), nb)
            if n:
                tbl[s, :n] = t[:n]
        return tbl

    def _paged_read_indices(self, slot_index: int,
                            rows: int) -> np.ndarray:
        """Flat pool-row indices of one slot's logical positions
        0..rows (park slice / prefill gather region). Positions past
        the slot's table read pool row 0 — always masked or trimmed by
        the consumer."""
        bs = self.kv_block_size
        t = self._kv_blocks.table(slot_index)
        nb = -(-rows // bs)
        blocks = np.zeros((nb,), np.int64)
        n = min(len(t), nb)
        if n:
            blocks[:n] = t[:n]
        idx = (blocks[:, None] * bs
               + np.arange(bs)[None, :]).reshape(-1)[:rows]
        return idx.astype(np.int32)

    def _paged_write_indices(self, slot_index: int, start: int,
                             count: int) -> np.ndarray:
        """Flat pool-row indices for writing positions
        start..start+count (prefill chunk scatter). Every position must
        already have an allocated block (``ensure`` ran); positions
        past max_len get DISTINCT out-of-range indices and drop."""
        bs = self.kv_block_size
        t = self._kv_blocks.table(slot_index)
        pool_rows = self.kv_pool_blocks * bs
        out = np.empty((count,), np.int64)
        for i in range(count):
            pos = start + i
            blk = pos // bs
            if blk < len(t):
                out[i] = t[blk] * bs + pos % bs
            else:
                out[i] = pool_rows + slot_index * self.max_len + pos
        return out.astype(np.int32)

    def _paged_oob_indices(self, row: int, count: int) -> np.ndarray:
        """DISTINCT out-of-range flat indices for a padding row's
        scatter (mode="drop" + unique_indices needs them distinct even
        though they never land)."""
        base = (self.kv_pool_blocks * self.kv_block_size
                + (self.num_slots + row) * self.max_len)
        return (base + np.arange(count)).astype(np.int32)

    def _get_block_copy_fn(self):
        """Copy one block's rows (all layers, + scale rows on the
        quantized tier) between flat-pool offsets — the copy-on-write
        primitive behind partial-tail aliasing and divergence COW. One
        executable total, vs the dense tier's per-length prefix-copy
        family."""
        key = ("pblockcopy",)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        self._note_compile("kv_block_copy",
                           block_size=self.kv_block_size,
                           **self._kvq_attrs)
        bs = self.kv_block_size
        shape = (self.cfg.num_layers, bs, self.cfg.num_kv_heads,
                 self.cfg.head_dim)
        sshape = (self.cfg.num_layers, bs, self.kv_scale_granule)
        kvq = self.kv_quant

        @partial(jax.jit, donate_argnums=(0,))
        def block_copy(cache: KVCache, src_row, dst_row):
            rk = jax.lax.dynamic_slice(cache.k, (0, src_row, 0, 0),
                                       shape)
            rv = jax.lax.dynamic_slice(cache.v, (0, src_row, 0, 0),
                                       shape)
            new_k = jax.lax.dynamic_update_slice(cache.k, rk,
                                                 (0, dst_row, 0, 0))
            new_v = jax.lax.dynamic_update_slice(cache.v, rv,
                                                 (0, dst_row, 0, 0))
            if not kvq:
                return KVCache(new_k, new_v)
            rks = jax.lax.dynamic_slice(cache.k_scale,
                                        (0, src_row, 0), sshape)
            rvs = jax.lax.dynamic_slice(cache.v_scale,
                                        (0, src_row, 0), sshape)
            return KVCache(
                new_k, new_v,
                jax.lax.dynamic_update_slice(cache.k_scale, rks,
                                             (0, dst_row, 0)),
                jax.lax.dynamic_update_slice(cache.v_scale, rvs,
                                             (0, dst_row, 0)))

        self._prefill_fns[key] = block_copy
        return block_copy

    def _paged_copy_block(self, src_blk: int, dst_blk: int) -> None:
        bs = self.kv_block_size
        t0 = time.monotonic()
        self.cache = self._get_block_copy_fn()(
            self.cache, np.int32(src_blk * bs), np.int32(dst_blk * bs))
        if self._tracer.enabled:
            self._tracer.step(
                "engine_op", t0, time.monotonic(),
                kind="kv_block_copy",
                program=program_key("kv_block_copy", block_size=bs,
                                    **self._kvq_attrs))

    def _paged_sync_resident(self, slot: Slot) -> None:
        """Reconcile the slot's block table with its (possibly just
        truncated) trusted history: drop blocks past kv_written, and
        copy-on-write the tail block when it is shared and the next
        write would land inside it — an aliased prefix must never be
        written through."""
        alloc = self._kv_blocks
        kvw = slot.kv_written
        alloc.truncate(slot.index, kvw)
        tail = kvw % self.kv_block_size
        if tail and alloc.tail_shared(slot.index):
            pair = alloc.cow_tail(slot.index)
            if pair is None:
                # Pool empty: fall back to the block boundary — the
                # dropped tail rows re-prefill (never corrupt a shared
                # block over an allocation failure).
                aligned = kvw - tail
                slot.tokens = slot.tokens[:aligned]
                slot.kv_written = aligned
                alloc.truncate(slot.index, aligned)
                return
            self._paged_copy_block(*pair)

    def _paged_alias(self, src: Slot | None, slot: Slot,
                     share: int) -> int:
        """The paged shared-prefix stamp: alias the source's full
        blocks into this fresh slot's table (refcount bump, ZERO row
        copies) and copy-on-write the partially shared tail block.
        Returns the prompt tokens now resident."""
        if src is None or share < 16:
            return 0
        bs = self.kv_block_size
        alloc = self._kv_blocks
        full, tail = divmod(share, bs)
        n = alloc.alias(src.index, slot.index, full) if full else 0
        reused = n * bs
        if n == full and tail:
            blk = alloc.append_block(slot.index)
            if blk is not None:
                src_blk = alloc.table(src.index)[full]
                self._paged_copy_block(src_blk, blk)
                alloc.cow_copies += 1
                reused += tail
        return reused

    # ---------------- radix prefix cache ----------------
    # (kvcache/radix.py; docs/KVCACHE.md "Automatic prefix cache".)

    def _radix_insert_slot(self, slot: Slot) -> int:
        """Donate a slot's clean (fully written) prefix blocks to the
        radix tree. The tree takes allocator holds on blocks it did
        not already cache, so they survive the slot's release. Engine
        thread only; no device work."""
        tree = self._kv_radix
        if tree is None or slot.session_id is None:
            return 0
        kept = min(slot.kv_written, len(slot.tokens))
        if kept < self.kv_block_size:
            return 0
        return tree.insert(slot.tokens,
                           self._kv_blocks.table(slot.index),
                           written=kept)

    def _radix_admit(self, req: _Request, slot: Slot,
                     prompt: list[int]) -> int:
        """Alias the longest radix-cached block chain into this fresh
        slot (zero device copies; the delta prefills from a block
        boundary, so no COW is needed at match time). On a tree miss,
        the legacy cross-slot scan SEEDS the tree — the explicit stamp
        path is now a thin shim over radix insert — and the match
        retries. Returns leading prompt tokens now resident (0 = no
        usable chain, or a longer parked host entry should restore
        instead)."""
        tree = self._kv_radix
        if tree is None:
            return 0
        bs = self.kv_block_size
        # At least one prompt token must run through the model (same
        # trust rule as every other reuse path).
        max_blocks = (len(prompt) - 1) // bs
        if max_blocks <= 0:
            return 0
        blocks, _digest = tree.match(prompt, max_blocks=max_blocks)
        matched = len(blocks) * bs
        if self.shared_prefix and matched < max_blocks * bs:
            # Tree shorter than another slot's resident prefix: donate
            # that slot's clean blocks, then match again.
            src, share = self.slots.best_shared_prefix(slot, prompt)
            if src is not None \
                    and min(share, src.kv_written) // bs * bs > matched:
                self._radix_insert_slot(src)
                blocks, _digest = tree.match(
                    prompt, max_blocks=max_blocks, count=False)
                matched = len(blocks) * bs
        if matched < bs:
            return 0
        if self._kv_pool.enabled:
            # Host-offload interplay: a LONGER parked entry for this
            # session wins — one H2D copy beats prefilling the extra
            # delta; _try_restore runs next in the caller.
            entry = self._kv_pool.get(req.session_id)
            if entry is not None:
                hm = _lcp(entry.tokens, prompt,
                          min(entry.kept, len(prompt) - 1))
                if hm > matched and self._kv_policy.should_restore(
                        hm, entry.nbytes):
                    return 0
        self._kv_blocks.alias_blocks(slot.index, blocks)
        slot.tokens = list(prompt[:matched])
        slot.kv_written = matched
        tree.note_hit(matched)
        self._m_shared.inc(matched)
        return matched

    def _paged_reserve_tokens(self, req: _Request) -> int:
        """Decode-growth reserve the admission check must see free
        (KV_RESERVE_POLICY): 'fixed' covers the next
        KV_RESERVE_TOKENS of growth, 'max_tokens' the request's whole
        budget, 'none' admits on prefill fit alone (maximum packing,
        relies on mid-decode shedding)."""
        if self.kv_reserve_policy == "none":
            return 0
        if self.kv_reserve_policy == "max_tokens":
            return req.params.max_tokens
        return min(self.kv_reserve_tokens, req.params.max_tokens)

    def _paged_admissible(self, slot: Slot, req: _Request,
                          reused: int, todo: int) -> bool:
        """A request is admissible iff its prefill blocks fit now and
        the reserve policy's decode-growth horizon is also free
        (ROADMAP item 1's admission-by-blocks-in-use). Rejections shed
        with retry_after — the same taxonomy as a queue shed, so
        clients back off instead of erroring."""
        bs = self.kv_block_size
        # The prefill pads its LAST chunk to a bucket: admission must
        # cover that padded write horizon — reused + the full chunks +
        # the final chunk's bucket — not todo plus a whole extra
        # bucket (which would demand up to 2x the blocks prefill ever
        # ensures and shed requests that fit).
        last = (todo % self.prefill_chunk
                or min(max(1, todo), self.prefill_chunk))
        pad = next((b for b in _PREFILL_BUCKETS if b >= last),
                   _PREFILL_BUCKETS[-1])
        need_tokens = min(self.max_len,
                          reused + max(0, todo - last) + pad
                          + self._paged_reserve_tokens(req))
        need = blocks_for(need_tokens, bs) \
            - self._kv_blocks.slot_blocks(slot.index)
        avail = self._kv_blocks.available()
        if self._kv_radix is not None:
            # Unreferenced radix-held blocks are reclaimable on demand
            # (the allocator's pressure callback evicts them inside
            # _take), so admission counts them as free.
            avail += self._kv_radix.evictable_blocks()
        if need <= avail:
            return True
        self._paged_exhausted_finish(
            req, f"KV block pool exhausted: prompt needs {need} more "
                 f"{bs}-token blocks ({self._kv_blocks.available()} "
                 f"free of {self.kv_pool_blocks})")
        return False

    def _paged_retry_after(self) -> float:
        """Back-off hint for a block-exhaustion shed: roughly one
        service time must elapse for a running generation to finish
        and free its blocks."""
        ema = self._sched.stats().get("service_time_ema_s") or 0.0
        return round(max(0.5, float(ema)), 2)

    def _paged_exhausted_finish(self, req: _Request,
                                error: str) -> None:
        self._events.emit("kv_pressure", severity="warning",
                          coalesce_s=10.0, coalesce_key="blocks",
                          reason="block_pool_exhausted",
                          free=self._kv_blocks.available(),
                          total=self.kv_pool_blocks)
        self._finish(req, "error", error=error,
                     code="kv_blocks_exhausted",
                     retry_after=self._paged_retry_after())

    def _paged_wait_penalty(self, prompt_len: int) -> float:
        """Block-pressure term for the scheduler's estimated-wait shed
        (asyncio side, racy-read tolerable — it's an estimate): when
        the pool cannot currently hold this prompt, at least one
        running generation must finish first, so the wait estimate
        grows by ~one service time."""
        if not self.paged:
            return 0.0
        need = blocks_for(prompt_len, self.kv_block_size)
        avail = self._kv_blocks.available()
        if self._kv_radix is not None:
            avail += self._kv_radix.evictable_blocks()
        if need <= avail:
            return 0.0
        return self._paged_retry_after()

    def _paged_prepare_decode(self, worst_adv: int) -> bool:
        """Pre-allocate every running slot's blocks out to its worst-
        case write horizon for the next decode call (device positions
        lead the host mirrors by the in-flight calls' advances). On
        pool exhaustion, sheds the youngest running request (frees its
        blocks via session release) and retries — the rehearsed
        degradation, never a crash. Returns False when nothing is left
        to run. MUST run before _patch_slot_state so a shed's
        deactivation reaches the very next call."""
        lead = sum(self._paged_leads) + worst_adv
        while self._running:
            victim: _Request | None = None
            for s, req in list(self._running.items()):
                horizon = min(self.max_len,
                              int(self._positions[s]) + lead)
                if not self._kv_blocks.ensure(s, horizon):
                    victim = max(self._running.values(),
                                 key=lambda r: r.admitted_at or 0.0)
                    break
            if victim is None:
                return True
            log.warning(
                f"KV block pool exhausted mid-decode; shedding "
                f"{victim.request_id}")
            slot = victim.slot
            self._paged_exhausted_finish(
                victim, "KV block pool exhausted mid-decode: request "
                        "shed to free blocks")
            if slot is not None and slot.session_id is not None:
                # The shed must actually free blocks: drop the
                # session's residency (its next turn re-prefills).
                self.slots.release_session(slot.session_id)
                self._kv_pool.purge(slot.session_id)
        return False

    def _kv_read_rows(self, snapshot, kv_len: int) -> int:
        """KV rows one decode step actually streamed, for the perf
        ledger's bandwidth figure. Dense: the fixed shapes read the
        whole bucket for every slot. Paged: only blocks backing live
        rows are read (the block walk prunes per slot), so the ledger
        counts blocks-read — this is what stops /perf bw_util
        over-reporting a mixed-length batch as S x bucket traffic."""
        if not self.paged:
            return self.num_slots * kv_len
        bs = self.kv_block_size
        return sum(
            min(kv_len, blocks_for(int(self._positions[s]), bs) * bs)
            for s, _ in snapshot)

    def _paged_decode_args(self, kv_len: int):
        """The block-table extra argument for a paged decode dispatch
        (empty tuple on the dense tier, so call sites stay shared)."""
        if not self.paged:
            return ()
        nb = kv_len // self.kv_block_size
        return (self._arg(self._paged_table_np(nb)),)

    # ---------------- structured decoding ----------------
    # (fasttalk_tpu/structured/; docs/STRUCTURED.md)

    def _get_st_compiler(self) -> FSMCompiler:
        """The (schema, tokenizer) FSM compiler+cache. Lazy and lock-
        guarded: first touched from the asyncio side (generate), and a
        plain-serving engine never builds the vocab byte table at
        all — the subsystem stays zero-cost until first use."""
        if self._st_compiler is None:
            with self._st_compiler_lock:
                if self._st_compiler is None:
                    self._st_compiler = FSMCompiler(
                        self.tokenizer,
                        cache_size=self._st_cfg["cache_size"],
                        max_states=self._st_cfg["max_states"],
                        json_depth=self._st_cfg["json_depth"],
                        sample_vocab=self.sample_vocab)
        return self._st_compiler

    def _st_register(self, req: _Request) -> None:
        """Pin a constrained request's FSM into the device union arena
        (engine thread, at admission). Growing the arena re-packs state
        offsets, so with constrained calls in flight the pipeline is
        drained first — the host FSM mirrors become authoritative and
        the refreshed per-slot states cannot rewind the device copy.
        Raises ArenaFull when running requests pin the whole budget."""
        if self._st_arena is None:
            self._st_arena = FSMArena(
                self.sample_vocab,
                tuple(sorted(t for t in self.tokenizer.eos_ids
                             if 0 <= t < self.sample_vocab)),
                self.num_slots,
                state_budget=self._st_cfg["state_budget"])
        arena = self._st_arena
        before = arena.state_cap
        req.fsm_entry = arena.register(req.fsm)
        if arena.dirty:
            if any(r.fsm is not None for _, r in
                   [p for call in self._inflight for p in call[3]]):
                while self._inflight:
                    self._retire_oldest()
            if any(r.fsm is not None for _, _, r in
                   [e for _, ents in self._pending_firsts
                    for e in ents]):
                self._drain_firsts(block=True)
            self._st_upload_tables()
            # Offsets may have moved: refresh every ACTIVE constrained
            # slot's device state from the (now-authoritative) host
            # mirrors.
            for s, r in self._running.items():
                if r.fsm is not None and r.fsm_entry is not None:
                    self._st_sel[s] = r.fsm_entry.sel
                    self._st_dirty.add(s)
            if arena.state_cap != before:
                # New table shapes: the constrained decode executables
                # key on them (one compile per capacity bucket).
                self._note_compile("structured_tables",
                                   states=arena.state_cap,
                                   classes=arena.class_cap)

    def _st_upload_tables(self) -> None:
        arena = self._st_arena
        self._st_masks_dev = self._put(arena.masks)
        self._st_nexts_dev = self._put(arena.nexts)
        self._st_cls_dev = self._put(arena.cls)
        arena.dirty = False

    def _st_release(self, req: _Request) -> None:
        """Terminal-path cleanup for a constrained request (inside
        _finish): unpin the FSM (tables stay cached for the next
        request of the same schema) and park the slot's device state
        back in FREE so a later unconstrained occupant is untouched."""
        self._st_jf_pending.discard(req.request_id)
        if req.fsm_entry is not None and self._st_arena is not None:
            self._st_arena.release(req.fsm)
            req.fsm_entry = None
        slot = req.slot
        if slot is not None:
            self._st_sel[slot.index] = 0
            self._st_dirty.add(slot.index)

    def _st_global_state(self, slot_index: int) -> int:
        req = self._running.get(slot_index)
        if req is None or req.fsm is None or req.fsm_entry is None:
            return 0  # FREE
        return self._st_arena.global_state(req.fsm_entry,
                                           req.fsm_state)

    def _get_st_patch_fn(self):
        """Scatter host-authoritative FSM states onto the chained
        device vector (finish→FREE resets, arena-repack refreshes)."""
        if self._st_patch_fn is None:
            @partial(jax.jit, donate_argnums=(1,))
            def st_patch(packed, fst):
                dirty = packed[:, 0] > 0.5
                return jnp.where(dirty, packed[:, 1].astype(fst.dtype),
                                 fst)

            self._st_patch_fn = st_patch
        return self._st_patch_fn

    def _get_st_sample_fn(self):
        """Masked sample-and-place: complete a constrained prefill (or
        a jump-forward) by sampling the next token under the packed
        allowed-row of the request's current FSM state, scattering it
        into the decode chain's current-token vector AND advancing the
        slot's device FSM state — one program, no host round trip
        before the first decode call."""
        if self._st_sample_fn is None:
            self._note_compile("st_sample")
            sv = self.sample_vocab
            widx = jnp.arange(sv) // 32
            wsh = (jnp.arange(sv) % 32).astype(jnp.uint32)

            @partial(jax.jit, donate_argnums=(1, 2))
            def st_sample(last_logits, cur, fst, rng, cfg_row,
                          mask_row, cls, nexts):
                slot = cfg_row[0].astype(jnp.int32)
                state = cfg_row[4].astype(jnp.int32)
                sel = cfg_row[5].astype(jnp.int32)
                rng, sub = jax.random.split(rng)
                allow = ((mask_row[widx] >> wsh)
                         & jnp.uint32(1)).astype(bool)
                lg = jnp.where(allow,
                               last_logits[:sv].astype(jnp.float32),
                               jnp.float32(-1e30))
                tok = sample_tokens(
                    lg[None], sub, cfg_row[1][None],
                    cfg_row[2].astype(jnp.int32)[None],
                    cfg_row[3][None], method=self.sampling_method)
                ns = nexts[state, cls[sel, tok[0]]]
                return (tok, cur.at[slot].set(tok[0], mode="drop"),
                        fst.at[slot].set(ns, mode="drop"), rng)

            self._st_sample_fn = st_sample
        return self._st_sample_fn

    def _st_sample_place(self, req: _Request, slot: Slot,
                         last_logits: Any) -> None:
        """Run the masked sample-place for one constrained slot and
        queue the token's emission (same deferred-fetch discipline as
        plain prefill completion)."""
        entry = req.fsm_entry
        gstate = self._st_arena.global_state(entry, req.fsm_state)
        mask_row = pack_mask_row(req.fsm, req.fsm_state,
                                 self._st_arena.words,
                                 req.fsm.eos_ids)
        cfg_row = np.array([slot.index, req.params.temperature,
                            req.params.top_k, req.params.top_p,
                            gstate, entry.sel], np.float32)
        t0 = time.monotonic()
        first, self._cur_tokens, self._st_state_dev, self._rng_dev = \
            self._get_st_sample_fn()(
                last_logits, self._cur_tokens, self._st_state_dev,
                self._rng_dev, self._arg(cfg_row),
                self._arg(mask_row), self._st_cls_dev,
                self._st_nexts_dev)
        if self._tracer.enabled:
            self._tracer.step("engine_op", t0, time.monotonic(),
                              kind="st_sample",
                              program=program_key("st_sample"))
        # The program just wrote this slot's authoritative state
        # (post-first-token). A pending host-side patch for the slot —
        # the previous occupant's finish→FREE reset, queued before
        # this admission — is now obsolete and would REWIND the device
        # FSM by one token (the host mirror lags until the deferred
        # first-token fetch drains): drop it.
        self._st_dirty.discard(slot.index)
        self._defer_first(first, [(0, slot.index, req)])

    def _st_penalties_neutral(self, req: _Request) -> bool:
        p = req.params
        return (p.repeat_penalty == 1.0 and p.presence_penalty == 0.0
                and p.frequency_penalty == 0.0)

    def _st_note_jump_candidate(self, req: _Request) -> None:
        """Called per consumed token for constrained requests: when the
        new state opens a forced single-transition chain long enough to
        beat one pipeline bubble, queue a jump. Jump-forward needs
        neutral penalties (forced tokens bypass the on-device count
        maintenance); with penalties active the decode steps still emit
        the same forced tokens — only the speed-up is skipped."""
        if self._st_jf_min <= 0 or not self._st_penalties_neutral(req):
            return
        if req.fsm_state < 0 \
                or int(req.fsm.forced_tok[req.fsm_state]) < 0:
            return  # DONE/DEAD sentinel, or not a forced state
        chain, _ = req.fsm.forced_chain(req.fsm_state)
        if len(chain) >= self._st_jf_min:
            self._st_jf_pending.add(req.request_id)

    def _st_jump_forward(self) -> None:
        """SGLang-style compressed-FSM jump: when a constrained slot's
        FSM state has a single outgoing transition chain, emit the
        forced tokens directly — one prefill call writes their KV rows
        (model steps skipped entirely), the text streams immediately,
        and a masked sample from the chain-end state restarts ordinary
        decoding. Runs only with the pipeline empty, so the host FSM
        mirrors are authoritative and no in-flight call can double-emit
        the chain."""
        self._drain_firsts(block=True)
        pending, self._st_jf_pending = self._st_jf_pending, set()
        for rid in pending:
            req = self._by_id.get(rid)
            if req is None or req.finished or req.slot is None:
                continue
            slot = req.slot
            if self._running.get(slot.index) is not req:
                continue
            chain, _end = req.fsm.forced_chain(req.fsm_state)
            room = min(req.params.max_tokens - req.generated,
                       self.usable_len - len(slot.tokens) - 1,
                       self.prefill_chunk - 1)
            n = min(len(chain), room)
            if n < self._st_jf_min:
                continue
            chain = chain[:n]
            start = int(self._positions[slot.index])
            # Feed the not-yet-fed newest token plus the whole chain:
            # the returned last-token logits then predict the token
            # AFTER the chain — exactly what the masked sample needs.
            feed = [slot.tokens[-1]] + chain
            bucket = next((b for b in _PREFILL_BUCKETS
                           if b >= len(feed)), None)
            if bucket is None or start + bucket > self.max_len:
                continue  # no room: plain decode emits the chain
            if self.paged and not self._kv_blocks.ensure(
                    slot.index, start + bucket):
                continue  # no blocks: plain decode emits the chain
            t0 = time.monotonic()
            padded = np.zeros((bucket,), np.int32)
            padded[:len(feed)] = feed
            last_logits = self._run_chunk_prefill(
                slot, padded, start, n, bucket)
            self._positions[slot.index] = start + n + 1
            slot.kv_written = start + n + 1
            self._dirty_slots.add(slot.index)
            for tok in chain:
                if req.finished \
                        or self._running.get(slot.index) is not req:
                    break
                self._consume_token(req, tok)
                req.jump_tokens += 1
                self._m_st_jump.inc()
            self._flush_emit(req)
            if self._tracer.enabled:
                self._tracer.step(
                    "engine_prefill", t0, time.monotonic(),
                    bucket=bucket, tokens=len(feed), rows=bucket,
                    kind="jump_forward",
                    program=self._prefill_program(start, bucket),
                    flops=self._perf.call_flops(len(feed), start + n))
                self._tracer.add_span(
                    req.request_id, "jump_forward", t0,
                    time.monotonic(), tokens=n)
            if req.finished:
                continue
            self._st_sample_place(req, slot, last_logits)

    def _get_prefill_fn(self, chunk: int):
        fn = self._prefill_fns.get(chunk)
        if fn is not None:
            return fn
        self._note_compile("prefill", chunk=chunk, **self._kvq_attrs)
        kvq = self.kv_quant
        sslot_shape = (self.cfg.num_layers, 1, self.max_len,
                       self.kv_scale_granule)

        @partial(jax.jit, donate_argnums=(1,))
        def prefill_step(params, cache: KVCache, tokens, start, slot,
                         last_index):
            """Run one prompt chunk for one slot; returns last-token logits."""
            slot_shape = (self.cfg.num_layers, 1, self.max_len,
                          self.cfg.num_kv_heads, self.cfg.head_dim)
            lk = jax.lax.dynamic_slice(cache.k, (0, slot, 0, 0, 0), slot_shape)
            lv = jax.lax.dynamic_slice(cache.v, (0, slot, 0, 0, 0), slot_shape)
            if kvq:
                lks = jax.lax.dynamic_slice(cache.k_scale,
                                            (0, slot, 0, 0), sslot_shape)
                lvs = jax.lax.dynamic_slice(cache.v_scale,
                                            (0, slot, 0, 0), sslot_shape)
                small = KVCache(lk, lv, lks, lvs)
            else:
                small = KVCache(lk, lv)
            positions = start + jnp.arange(chunk)[None, :]
            logits, updated = forward(
                params, self.cfg, tokens[None, :], positions,
                small, start[None], blockwise=True,
                pallas_int8=self.use_pallas_int8,
                pallas_int4=self.use_pallas_int4,
                logits_indices=last_index[None])
            new_k = jax.lax.dynamic_update_slice(
                cache.k, updated.k, (0, slot, 0, 0, 0))
            new_v = jax.lax.dynamic_update_slice(
                cache.v, updated.v, (0, slot, 0, 0, 0))
            if kvq:
                return KVCache(
                    new_k, new_v,
                    jax.lax.dynamic_update_slice(
                        cache.k_scale, updated.k_scale, (0, slot, 0, 0)),
                    jax.lax.dynamic_update_slice(
                        cache.v_scale, updated.v_scale,
                        (0, slot, 0, 0))), logits[0, 0]
            return KVCache(new_k, new_v), logits[0, 0]

        self._prefill_fns[chunk] = prefill_step
        return prefill_step

    def _get_paged_prefill_fn(self, chunk: int, ctx: int):
        """Paged single-slot prompt chunk: gather the slot's logical
        0..ctx rows out of the flat pool (read_idx, host-built from
        the block table), run the UNCHANGED dense ``forward`` over the
        contiguous scratch region, then scatter only the chunk's
        written rows back through write_idx — gather-run-scatter is
        the same structure the dense batched path already uses for
        slot rows, so the model code needs no paged prefill variant.
        ``ctx`` is a KV bucket covering start+chunk."""
        key = ("pprefill", chunk, ctx)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        self._note_compile("prefill", chunk=chunk, ctx=ctx,
                           kv_layout="paged", **self._kvq_attrs)
        kvq = self.kv_quant

        @partial(jax.jit, donate_argnums=(1,))
        def paged_prefill_step(params, cache: KVCache, tokens, start,
                               read_idx, write_idx, last_index):
            gk = cache.k[:, read_idx][:, None]  # [L, 1, ctx, Kv, H]
            gv = cache.v[:, read_idx][:, None]
            if kvq:
                small = KVCache(gk, gv,
                                cache.k_scale[:, read_idx][:, None],
                                cache.v_scale[:, read_idx][:, None])
            else:
                small = KVCache(gk, gv)
            positions = start + jnp.arange(chunk)[None, :]
            logits, upd = forward(
                params, self.cfg, tokens[None, :], positions,
                small, start[None], blockwise=True,
                pallas_int8=self.use_pallas_int8,
                pallas_int4=self.use_pallas_int4,
                logits_indices=last_index[None])

            def written(arr):  # [L, 1, ctx, ...] -> the chunk's rows
                sizes = (arr.shape[0], 1, chunk) + arr.shape[3:]
                zeros = (0,) * (arr.ndim - 3)
                return jax.lax.dynamic_slice(
                    arr, (0, 0, start) + zeros, sizes)[:, 0]

            new_k = cache.k.at[:, write_idx].set(
                written(upd.k), mode="drop", unique_indices=True)
            new_v = cache.v.at[:, write_idx].set(
                written(upd.v), mode="drop", unique_indices=True)
            if kvq:
                return KVCache(
                    new_k, new_v,
                    cache.k_scale.at[:, write_idx].set(
                        written(upd.k_scale), mode="drop",
                        unique_indices=True),
                    cache.v_scale.at[:, write_idx].set(
                        written(upd.v_scale), mode="drop",
                        unique_indices=True)), logits[0, 0]
            return KVCache(new_k, new_v), logits[0, 0]

        self._prefill_fns[key] = paged_prefill_step
        return paged_prefill_step

    def _run_chunk_prefill(self, slot: Slot, padded: np.ndarray,
                           start: int, last_index: int, bucket: int):
        """Dispatch one single-slot prefill chunk on the layout's
        program (dense slot slice or paged gather/scatter) and return
        the last-token logits. Paged callers must have ensured blocks
        for start+bucket."""
        if self.paged:
            ctx = next((b for b in _KV_BUCKETS
                        if b >= start + bucket and b <= self.max_len),
                       self.max_len)
            fn = self._get_paged_prefill_fn(bucket, ctx)
            self.cache, last = fn(
                self.params, self.cache, self._arg(padded),
                np.int32(start),
                self._arg(self._paged_read_indices(slot.index, ctx)),
                self._arg(self._paged_write_indices(slot.index, start,
                                                    bucket)),
                np.int32(last_index))
            return last
        fn = self._get_prefill_fn(bucket)
        self.cache, last = fn(self.params, self.cache,
                              self._arg(padded), np.int32(start),
                              np.int32(slot.index),
                              np.int32(last_index))
        return last

    def _ring_prefill_eligible(self, start: int, n_tokens: int) -> int:
        """If this fresh prompt should prefill through ring attention,
        return its (power-of-two) bucket; else 0.

        Eligible when the engine runs on a mesh with sp > 1, the prompt
        starts a fresh slot (ring attention is pure self-attention —
        a non-zero start would need cache rows the ring never visits),
        and it is long enough that one chip's attention working set is
        the thing to avoid (>= max_len/sp — the per-chip KV shard; the
        module's O(T/sp) memory promise, parallel/ring_attention.py).
        """
        if self.mesh is None or start != 0:
            return 0
        sp = self.mesh.shape.get("sp", 1)
        if sp <= 1 or n_tokens < max(256, self.max_len // sp):
            return 0
        bucket = 1 << (n_tokens - 1).bit_length()  # next power of two
        bucket = max(bucket, 2 * sp)
        if bucket > self.max_len or bucket % sp:
            return 0
        return bucket

    def _get_ring_prefill_fn(self, bucket: int):
        """Whole-prompt prefill for ONE slot with attention routed
        through parallel.ring_attention (VERDICT r4 #4): Q/K/V stay
        sequence-sharded over "sp" and K/V blocks rotate the ICI ring,
        so per-chip attention memory is O(T/sp) — where the default
        GSPMD lowering all-gathers K/V per chip. K/V are also written
        into the slot's (sp-sharded) cache rows, so decode attends the
        exact rows the ring produced. Single call for the full
        (bucketed) prompt — chunked prefill cannot ride the ring, since
        a later chunk attends cache rows the rotation never visits."""
        key = ("ring", bucket)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        self._note_compile("ring_prefill", bucket=bucket)
        from fasttalk_tpu.parallel.train import ring_override

        ring = ring_override(self.mesh)

        @partial(jax.jit, donate_argnums=(1,))
        def ring_prefill(params, cache: KVCache, tokens, slot,
                         last_index):
            slot_shape = (self.cfg.num_layers, 1, self.max_len,
                          self.cfg.num_kv_heads, self.cfg.head_dim)
            lk = jax.lax.dynamic_slice(cache.k, (0, slot, 0, 0, 0),
                                       slot_shape)
            lv = jax.lax.dynamic_slice(cache.v, (0, slot, 0, 0, 0),
                                       slot_shape)
            positions = jnp.arange(bucket)[None, :]
            logits, updated = forward(
                params, self.cfg, tokens[None, :], positions,
                KVCache(lk, lv), jnp.zeros((1,), jnp.int32),
                attn_override=ring, override_write=True,
                logits_indices=last_index[None])
            new_k = jax.lax.dynamic_update_slice(
                cache.k, updated.k, (0, slot, 0, 0, 0))
            new_v = jax.lax.dynamic_update_slice(
                cache.v, updated.v, (0, slot, 0, 0, 0))
            return KVCache(new_k, new_v), logits[0, 0]

        self._prefill_fns[key] = ring_prefill
        return ring_prefill

    def _get_batched_prefill_fn(self, chunk: int, group: int, ctx: int):
        """One prompt chunk for ``group`` slots at once.

        Gathers the first ``ctx`` KV positions of the target slots (the
        forward never reads or writes past start+chunk <= ctx, and
        gathering full max_len rows would transiently double the KV
        cache's HBM), runs one [group, chunk] forward with per-row write
        offsets, scatters the region back. Padding rows carry
        write_mask=False and an out-of-range slot index, so their
        scatter is dropped.

        The per-row scalars travel in ONE packed f32 array (rowcfg
        [group, 7]: slot, start, last_idx, mask, temp, top_k, top_p —
        all exactly representable) and the sampled first tokens are
        scattered into the decode chain's current-token vector inside
        the same program: every extra transfer or eager op is its own
        dispatch, so the whole burst is one host→device call.
        """
        key = (chunk, group, ctx)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        self._note_compile("batched_prefill", chunk=chunk, group=group,
                           ctx=ctx, **self._kvq_attrs)
        replicate = self._replicate_sharding()
        kvq = self.kv_quant

        @partial(jax.jit, donate_argnums=(1,))
        def batched_prefill(params, cache: KVCache, tokens, rowcfg,
                            cur, rng):
            slot_idx = rowcfg[:, 0].astype(jnp.int32)
            starts = rowcfg[:, 1].astype(jnp.int32)
            last_idx = rowcfg[:, 2].astype(jnp.int32)
            mask = rowcfg[:, 3] > 0.5
            temps, topks, topps = (rowcfg[:, 4],
                                   rowcfg[:, 5].astype(jnp.int32),
                                   rowcfg[:, 6])
            gk = cache.k[:, slot_idx, :ctx]  # [L, group, ctx, Kv, H]
            gv = cache.v[:, slot_idx, :ctx]
            if kvq:
                small = KVCache(gk, gv,
                                cache.k_scale[:, slot_idx, :ctx],
                                cache.v_scale[:, slot_idx, :ctx])
            else:
                small = KVCache(gk, gv)
            positions = starts[:, None] + jnp.arange(chunk)[None, :]
            logits, upd = forward(
                params, self.cfg, tokens, positions, small,
                starts, blockwise=True, write_mask=mask,
                pallas_int8=self.use_pallas_int8,
                pallas_int4=self.use_pallas_int4,
                logits_indices=last_idx)
            new_k = cache.k.at[:, slot_idx, :ctx].set(
                upd.k, mode="drop", unique_indices=True)
            new_v = cache.v.at[:, slot_idx, :ctx].set(
                upd.v, mode="drop", unique_indices=True)
            new_ks = new_vs = None
            if kvq:
                new_ks = cache.k_scale.at[:, slot_idx, :ctx].set(
                    upd.k_scale, mode="drop", unique_indices=True)
                new_vs = cache.v_scale.at[:, slot_idx, :ctx].set(
                    upd.v_scale, mode="drop", unique_indices=True)
            # First-token sampling fused into the same call: one device
            # round-trip per burst instead of two (TTFT-critical).
            rng, sub = jax.random.split(rng)
            firsts = sample_tokens(logits[:, 0, :self.sample_vocab], sub,
                                   temps, topks, topps,
                                   method=self.sampling_method)
            new_cur = cur.at[slot_idx].set(firsts, mode="drop")
            if replicate is not None:  # host-fetched on every DCN host
                firsts = jax.lax.with_sharding_constraint(firsts,
                                                          replicate)
            return KVCache(new_k, new_v, new_ks, new_vs), firsts, \
                new_cur, rng

        self._prefill_fns[key] = batched_prefill
        return batched_prefill

    def _get_paged_batched_prefill_fn(self, chunk: int, group: int,
                                      ctx: int):
        """Paged variant of ``_get_batched_prefill_fn``: the group's
        KV regions gather through per-row flat pool indices (read_idx
        [group, ctx]) instead of slot ids, and each row's written
        chunk scatters back through write_idx [group, chunk] (padding
        rows carry distinct out-of-range indices and drop). The
        forward body, rowcfg packing and fused first-token sampling
        are identical to the dense program."""
        key = ("pbatch", chunk, group, ctx)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        self._note_compile("batched_prefill", chunk=chunk, group=group,
                           ctx=ctx, kv_layout="paged",
                           **self._kvq_attrs)
        kvq = self.kv_quant

        @partial(jax.jit, donate_argnums=(1,))
        def paged_batched_prefill(params, cache: KVCache, tokens,
                                  rowcfg, read_idx, write_idx, cur,
                                  rng):
            slot_idx = rowcfg[:, 0].astype(jnp.int32)
            starts = rowcfg[:, 1].astype(jnp.int32)
            last_idx = rowcfg[:, 2].astype(jnp.int32)
            mask = rowcfg[:, 3] > 0.5
            temps, topks, topps = (rowcfg[:, 4],
                                   rowcfg[:, 5].astype(jnp.int32),
                                   rowcfg[:, 6])
            gk = cache.k[:, read_idx]  # [L, group, ctx, Kv, H]
            gv = cache.v[:, read_idx]
            if kvq:
                small = KVCache(gk, gv,
                                cache.k_scale[:, read_idx],
                                cache.v_scale[:, read_idx])
            else:
                small = KVCache(gk, gv)
            positions = starts[:, None] + jnp.arange(chunk)[None, :]
            logits, upd = forward(
                params, self.cfg, tokens, positions, small,
                starts, blockwise=True, write_mask=mask,
                pallas_int8=self.use_pallas_int8,
                pallas_int4=self.use_pallas_int4,
                logits_indices=last_idx)
            sel = positions  # [group, chunk] region rows each row wrote

            def written(arr):  # [L, group, ctx, ...] -> chunk rows
                idx = sel.reshape((1,) + sel.shape
                                  + (1,) * (arr.ndim - 3))
                return jnp.take_along_axis(arr, idx, axis=2)

            new_k = cache.k.at[:, write_idx].set(
                written(upd.k), mode="drop", unique_indices=True)
            new_v = cache.v.at[:, write_idx].set(
                written(upd.v), mode="drop", unique_indices=True)
            new_ks = new_vs = None
            if kvq:
                new_ks = cache.k_scale.at[:, write_idx].set(
                    written(upd.k_scale), mode="drop",
                    unique_indices=True)
                new_vs = cache.v_scale.at[:, write_idx].set(
                    written(upd.v_scale), mode="drop",
                    unique_indices=True)
            rng, sub = jax.random.split(rng)
            firsts = sample_tokens(logits[:, 0, :self.sample_vocab], sub,
                                   temps, topks, topps,
                                   method=self.sampling_method)
            new_cur = cur.at[slot_idx].set(firsts, mode="drop")
            return KVCache(new_k, new_v, new_ks, new_vs), firsts, \
                new_cur, rng

        self._prefill_fns[key] = paged_batched_prefill
        return paged_batched_prefill

    def _get_patch_fn(self):
        """One jitted program applying all dirty-slot mirror changes:
        packed [S, 9] = (dirty, position, active, temp, top_k, top_p,
        repeat_penalty, presence_penalty, frequency_penalty). Dirty
        slots also get their penalty-count row zeroed (a slot goes dirty
        exactly at (re)admission and completion — both are generation
        boundaries, and penalties are per-generation). Composes with
        in-flight calls (it consumes the latest chained arrays) without
        draining the pipeline, and costs one transfer + one program
        instead of per-field eager scatters."""
        if self._patch_fn is None:
            @partial(jax.jit, donate_argnums=(1,))
            def apply_patch(packed, counts, pos, active, temps, topks,
                            topps, reps, press, freqs):
                dirty = packed[:, 0] > 0.5
                pos = jnp.where(dirty, packed[:, 1].astype(pos.dtype), pos)
                active = jnp.where(dirty, packed[:, 2] > 0.5, active)
                temps = jnp.where(dirty, packed[:, 3], temps)
                topks = jnp.where(dirty, packed[:, 4].astype(topks.dtype),
                                  topks)
                topps = jnp.where(dirty, packed[:, 5], topps)
                reps = jnp.where(dirty, packed[:, 6], reps)
                press = jnp.where(dirty, packed[:, 7], press)
                freqs = jnp.where(dirty, packed[:, 8], freqs)
                counts = jnp.where(dirty[:, None], 0, counts)
                return counts, pos, active, temps, topks, topps, \
                    reps, press, freqs

            self._patch_fn = apply_patch
        return self._patch_fn

    def _get_sample_place_fn(self):
        """Jitted completion of a single-slot long prefill: split the
        rng, sample the first token from the chunk's last logits and
        scatter it into the current-token vector — one program, no
        eager ops."""
        if self._sample_place_fn is None:
            replicate = self._replicate_sharding()

            @jax.jit
            def sample_place(last_logits, cur, rng, cfg_row):
                slot = cfg_row[0].astype(jnp.int32)
                rng, sub = jax.random.split(rng)
                first = sample_tokens(
                    last_logits[None, :self.sample_vocab], sub,
                    cfg_row[1][None],
                    cfg_row[2].astype(jnp.int32)[None], cfg_row[3][None],
                    method=self.sampling_method)
                if replicate is not None:
                    first = jax.lax.with_sharding_constraint(first,
                                                             replicate)
                return first, cur.at[slot].set(first[0], mode="drop"), rng

            self._sample_place_fn = sample_place
        return self._sample_place_fn

    # ---------------- engine thread ----------------

    def _run(self) -> None:
        log.info("engine thread started",
                 model=self.cfg.name, slots=self.num_slots,
                 max_len=self.max_len)
        try:
            while True:
                # Watchdog heartbeat: one float store per iteration
                # (GIL-atomic, no lock). The loop iterates at least
                # every 50 ms when idle (command-queue timeout), so a
                # stale stamp means a blocked device call, not idleness.
                self._hb_mono = time.monotonic()
                if _fp.enabled:
                    # Chaos seam (docs/RESILIENCE.md): crash_thread or
                    # hang the engine thread itself — the supervisor-
                    # restart and watchdog drills inject here.
                    _fp.fire("engine.loop.tick")
                idle = not self._running and not self._inflight \
                    and not self._prefilling and not self._pending_firsts
                if not self._drain_commands(block=idle):
                    break
                if len(self._sched):
                    if not self._running and not self._inflight \
                            and not self._prefilling:
                        # Burst coalescing: from idle, the first request
                        # of a concurrent burst arrives a few ms before
                        # the rest, and admitting it alone would queue a
                        # full decode call ahead of everyone else's
                        # prefill (traced: +387 ms first-token for the
                        # stragglers). A 3 ms grace drains the rest of
                        # the burst into ONE admission group; a solo
                        # request pays +3 ms TTFT.
                        stop = False
                        for _ in range(2):
                            time.sleep(0.003)
                            if not self._drain_commands(block=False):
                                stop = True
                                break
                        if stop:
                            break
                    self._admit()
                if self._prefilling:
                    # One chunk per iteration: long prompts interleave
                    # with decode calls instead of stalling every
                    # running session for their whole prefill. Safe
                    # without draining the pipeline: chunk writes target
                    # reserved slots and are ordered behind in-flight
                    # calls by the cache data dependency.
                    self._advance_prefill()
                if self._pending_firsts:
                    # Emit any first tokens whose async fetch has landed;
                    # block when nothing else would make progress — which
                    # includes running requests whose whole remaining
                    # budget IS the pending first token (max_tokens=1):
                    # no decode call will ever be dispatched for those,
                    # so a non-blocking poll here would spin forever.
                    idle_wait = not self._inflight and not (
                        self._running and self._should_dispatch())
                    self._drain_firsts(block=idle_wait)
                if self._st_jf_pending and not self._inflight:
                    # Jump-forward fires only on an empty pipeline (the
                    # host FSM mirrors are then authoritative); while a
                    # jump is pending, dispatch pauses below so the
                    # pipeline drains within one retirement. If the
                    # chain evaporates (state moved on), decoding
                    # resumes untouched — the mask makes the decode
                    # steps emit the forced tokens correctly either
                    # way; jump-forward is purely the fast path.
                    self._st_jump_forward()
                if self._running:
                    if self._should_dispatch() \
                            and not self._st_jf_pending:
                        self._dispatch_decode()
                        if len(self._inflight) >= self.pipeline_depth:
                            self._retire_oldest()
                    elif self._inflight:
                        self._retire_oldest()
                elif self._inflight:
                    # Retire ONE call per iteration, not the whole
                    # pipeline: a new request arriving while the tail of
                    # a finished generation drains would otherwise wait
                    # pipeline_depth × call-time before admission (the
                    # command queue is only read between iterations).
                    self._retire_oldest()
                self._m_active.set(len(self._running))
                self._m_queue.set(len(self._sched)
                                  + len(self._prefilling))
                self._kv_tick()
        except (_fp.FaultCrash, Exception) as e:
            # The engine thread must not die silently. FaultCrash is a
            # BaseException (so it escapes every scoped handler like a
            # real interpreter-level fault would), but a crash HERE
            # must still terminal-event the in-flight requests and set
            # _stopped — the supervisor-restart path depends on it.
            log.critical(f"engine thread crashed: {e}", exc_info=True)
            if self.call_sink is not None:
                # A published descriptor may precede the crash: tell
                # followers the cluster is dead rather than leaving
                # them blocked in their recv loop (the prefill paths
                # publish their own aborts; this covers the
                # decode/spec/patch family and anything unforeseen).
                try:
                    self._sink("abort", reason=f"engine crashed: {e}")
                except Exception:
                    pass
            self._abort_all(f"engine crashed: {e}")
        else:
            self._abort_all("engine shut down")
        finally:
            self._stopped.set()
            log.info("engine thread stopped")

    def _abort_all(self, reason: str) -> None:
        """Terminal-event every outstanding request so no caller awaits
        forever after a stop or crash."""
        for req in list(self._by_id.values()):
            with self._term_lock:  # see _finish: atomic vs force_fail
                if req.finished:
                    continue
                req.finished = True
            if req.fsm is not None:
                # Unpin from the FSM arena (the abort path bypasses
                # _finish): a leaked ref would pin the schema's states
                # for the engine's lifetime.
                self._st_release(req)
            self._record_slo(req, ok=False)
            self._emit(req, {"type": "error", "error": reason,
                             "code": "internal_error"})
        self._by_id.clear()
        self._sched.clear()
        self._prefilling.clear()
        self._running.clear()
        self._inflight.clear()
        self._pending_firsts.clear()
        self._paged_leads.clear()
        self._st_jf_pending.clear()

    def _drain_commands(self, block: bool) -> bool:
        """Process queued commands. Returns False on stop."""
        while True:
            try:
                cmd, arg = self._commands.get(timeout=0.05 if block else 0.0)
            except queue.Empty:
                return True
            block = False
            if cmd == "stop":
                return False
            if cmd == "kick":
                pass  # submission landed in the scheduler; just wake
            elif cmd == "cancel":
                req = self._by_id.get(arg)
                if req is not None:
                    req.cancelled = True
                    if self._sched.cancel(arg) is not None:
                        # Still queued: terminal event now, O(1) (the
                        # r1 list did a linear remove scan here).
                        self._finish(req, "cancelled")
            elif cmd == "release":
                # The session is over (WS disconnect / end_session):
                # its parked host KV must go too, or the pool leaks
                # entries for sessions that can never return (they
                # would sit until TTL, squeezing live sessions out of
                # the budget).
                self._kv_pool.purge(arg)
                slot = self.slots.lookup(arg)
                if slot is not None and slot.active:
                    self._release_after.add(arg)
                else:
                    self.slots.release_session(arg)

    def _expire_queued(self, now: float | None = None) -> None:
        """Terminal-event every queued request past its deadline — they
        must never touch the TPU (ISSUE 2: predictable degradation; a
        request that already blew its latency budget serves nobody)."""
        now = time.monotonic() if now is None else now
        # No explicit now to the sweep: expiry must be judged on the
        # SCHEDULER's clock (injectable for deterministic race tests),
        # which set the deadlines in the first place. The engine-side
        # `now` below only formats the waited-time message/span.
        for entry in self._sched.take_expired():
            req = entry.payload
            if req is None or req.finished:
                continue
            waited = now - req.submitted_at
            if self._tracer.enabled:
                self._tracer.add_span(req.request_id, "queue_wait",
                                      req.submitted_at, now,
                                      priority=entry.priority,
                                      expired=True)
            self._finish(
                req, "error",
                error=f"request expired after {waited:.1f}s in the "
                f"admission queue (deadline "
                f"{entry.deadline - entry.submitted_at:.1f}s)",
                code="deadline_expired",
                retry_after=self._sched.retry_after())

    def _admit(self) -> None:
        """Move waiting requests into free slots.

        Admission order is the scheduler's: priority class (with bulk
        aging), round-robin across sessions, deadlines enforced. A
        request whose session is still generating is skipped in O(1)
        (rotated, not scanned) rather than head-of-line blocking.
        Requests whose remaining prompt fits one prefill bucket (the
        common chat-turn case) are prefetched together in one batched
        device call — a burst of N arrivals costs one prefill + one
        sample round-trip instead of 2N (the reference serialised
        engine-side prefills the same way it serialised everything: one
        HTTP request at a time).
        """
        self._expire_queued()
        # The batched path normally caps prompts at prefill_chunk so a
        # long prefill cannot stall running sessions (chunked path
        # interleaves instead). From IDLE there is nobody to stall, and
        # the chunked path would serialize a cold burst of long prompts
        # at one link round trip per chunk (measured: 16 × ~600-token
        # personas took 5 s p50 TTFT through it) — so allow one batched
        # call up to the 1024 bucket, which also lets intra-batch
        # prefix sharing engage on exactly the long-persona bursts
        # where it pays.
        idle = not self._running and not self._inflight \
            and not self._prefilling
        allowed = max(self.prefill_chunk, 1024) if idle \
            else self.prefill_chunk
        batch: list[tuple[_Request, Slot, int, list[int]]] = []
        busy = {s.session_id for s in self.slots.slots
                if s.active and s.session_id is not None}
        while True:
            entry = self._sched.pop(busy)
            if entry is None:
                break
            req = entry.payload
            if req.finished:
                # Already terminal (errored by _abort_all during a
                # crash before this pop saw it): admitting it would
                # leak a slot on a request nobody consumes.
                continue
            if req.cancelled:  # cancelled before the drain saw it
                self._finish(req, "cancelled")
                continue
            slot = self.slots.acquire(req.session_id)
            if slot is not None and self._kv_pool.enabled:
                # Admission proves the session is alive: clear any
                # released-tombstone so later parks aren't refused
                # (engine-seam callers reuse ids after release).
                self._kv_pool.revive(req.session_id)
            if slot is None:
                # All slots actively decoding: keep the entry at the
                # head of its session's queue (deadline intact).
                self._sched.requeue_front(entry)
                break
            # Re-acquiring a slot still visible in an in-flight call is
            # safe without draining: the donated cache chains every call,
            # so the old call's garbage writes (all at positions >= the
            # kept length > the reused prefix) execute strictly before
            # this slot's fresh prefill, whose writes then win; the old
            # call's tokens are dropped at retirement by the snapshot
            # ownership check.
            # Reserve immediately: activation is deferred to after the
            # batched prefill, and an unreserved slot would be fair game
            # for eviction by the next acquire in this same loop.
            req.slot = slot
            slot.active = True
            busy.add(req.session_id)  # one admission per session
            req.admitted_at = time.monotonic()
            self._m_queue_wait.observe(
                (req.admitted_at - req.submitted_at) * 1000)
            if self._tracer.enabled:
                self._tracer.add_span(req.request_id, "queue_wait",
                                      req.submitted_at, req.admitted_at,
                                      slot=slot.index,
                                      priority=entry.priority)
                self._tracer.set_phase(req.request_id, "prefill")
            prompt = req.prompt_tokens
            reused = self.slots.reuse_prefix(slot, prompt)
            if self.paged:
                # Reconcile the block table with the (possibly just
                # truncated) history: free divergent blocks, and COW a
                # shared tail block before any write can land in it.
                self._paged_sync_resident(slot)
                reused = min(reused, slot.kv_written)
            if reused:
                self._m_prefix.inc(reused)
            if not reused:
                # Radix prefix cache (kvcache/radix.py): alias the
                # longest cached block chain — zero device copies,
                # zero explicit registration. Defers internally to a
                # LONGER parked host entry (restore beats prefilling
                # the extra delta).
                reused = self._radix_admit(req, slot, prompt)
            if not reused and (restored := self._try_restore(req, slot,
                                                             prompt)):
                # Host-offload tier: the session's kept prefix came
                # back from host RAM — only the token delta prefills
                # below, composing with the delta path exactly like
                # slot-resident reuse.
                reused = restored
            if not reused and self.shared_prefix:
                # Fresh slot: stamp the longest prefix resident in any
                # OTHER slot (common system prompt across sessions)
                # instead of re-prefilling it, aligned to the 16-token
                # stamp granule (_stamp_prefix decomposes the share
                # into pow2 chunks, so the copy executable family
                # stays bounded without the old pow2 round-down that
                # wasted up to half the match). The source's rows
                # [0:share) are stable: its own writes only ever
                # target positions >= its kept length.
                src, share = self.slots.best_shared_prefix(slot, prompt)
                if self.paged:
                    # Paged tier: block ALIASING, not row copies — the
                    # full shared blocks refcount-bump into this slot's
                    # table, only a partial tail block device-copies
                    # (COW). No pow2 granule needed: there is no
                    # per-length executable family to bound.
                    aliased = self._paged_alias(src, slot, share)
                    if aliased:
                        slot.tokens = list(prompt[:aliased])
                        slot.kv_written = aliased
                        reused = aliased
                        self._m_shared.inc(aliased)
                    src = None  # the dense stamp below must not run
                if src is not None \
                        and share >= self._STAMP_GRANULE:
                    stamped = self._stamp_prefix(src.index, slot.index,
                                                 share)
                    if stamped:
                        slot.tokens = list(prompt[:stamped])
                        slot.kv_written = stamped
                        reused = stamped
                        self._m_shared.inc(stamped)
            todo = prompt[reused:]
            req.prefill_tokens = len(todo)  # restore-policy cost feed
            if reused + len(todo) > self.usable_len:
                self._finish(req, "error",
                             error=f"prompt ({len(prompt)} tok) exceeds "
                             "context")
                continue
            if self.paged and not self._paged_admissible(
                    slot, req, reused, len(todo)):
                continue  # shed with retry_after (blocks don't fit)
            if req.fsm is not None:
                # Constrained admission: pin the FSM into the device
                # arena, then take the single-slot prefill path — its
                # completion samples the first token under the start-
                # state mask (the batched group's fused sampler is
                # unmasked). Structured requests are the minority; the
                # batched path stays untouched for everyone else.
                try:
                    self._st_register(req)
                except ArenaFull as e:
                    self._finish(req, "error", error=str(e),
                                 code="structured_capacity")
                    continue
                self._prefilling.append(
                    _PrefillState(req=req, slot=slot, start=reused,
                                  todo=todo))
                continue
            bucket = next((b for b in _PREFILL_BUCKETS if b >= len(todo)),
                          None)
            if bucket is not None and len(todo) <= allowed \
                    and reused + bucket <= self.max_len \
                    and not req.params.prefill_only \
                    and not self._ring_prefill_eligible(reused,
                                                        len(todo)):
                batch.append((req, slot, reused, todo))
            else:
                # Long prompts — and, on an sp>1 mesh, fresh prompts
                # past one chip's KV shard (ring-eligible) — go through
                # _advance_prefill.
                self._prefilling.append(
                    _PrefillState(req=req, slot=slot, start=reused,
                                  todo=todo))
        if batch:
            if self.shared_prefix and len(batch) >= 2:
                self._prefill_batched_shared(batch)
            else:
                self._prefill_batched(batch)
        # Entries the pop loop found expired must terminal-event NOW:
        # diverting the last queued entry drops the queue to empty, so
        # no later loop iteration would re-enter _admit to drain them.
        self._expire_queued()

    def _advance_prefill(self) -> None:
        """Run ONE chunk of the oldest in-progress long prefill."""
        # Sweep the WHOLE queue for cancelled/finished entries — a
        # cancel must free its reserved slot and emit its terminal event
        # immediately, not after every earlier long prefill completes.
        keep: list[_PrefillState] = []
        for st in self._prefilling:
            if st.req.finished:
                continue
            if st.req.cancelled:
                self._finish(st.req, "cancelled")
                continue
            keep.append(st)
        self._prefilling = keep
        if not self._prefilling:
            return
        st = self._prefilling[0]
        req, slot = st.req, st.slot
        try:
            if _fp.enabled:
                # Chaos seam: `error` is scoped to this request by the
                # handler below (the engine survives); `crash_thread`
                # escapes it (BaseException) and kills the thread.
                _fp.fire("engine.prefill.dispatch",
                         request_id=req.request_id,
                         session_id=req.session_id)
            ring_bucket = self._ring_prefill_eligible(st.start,
                                                      len(st.todo))
            t0p = time.monotonic()
            if ring_bucket:
                # Whole prompt in ONE ring-attention call: per-chip
                # attention memory O(T/sp) instead of the all-gather
                # form (see _get_ring_prefill_fn).
                n = len(st.todo)
                padded = np.zeros((ring_bucket,), np.int32)
                padded[:n] = st.todo
                fn = self._get_ring_prefill_fn(ring_bucket)
                self._sink("ring_prefill", bucket=ring_bucket,
                           tokens=padded, slot=slot.index, last=n - 1)
                self.cache, st.last_logits = fn(
                    self.params, self.cache, self._arg(padded),
                    np.int32(slot.index), np.int32(n - 1))
                slot.tokens.extend(st.todo)
                st.start = n
                slot.kv_written = n
                st.todo = []
                self._tracer.step(
                    "engine_prefill", t0p, time.monotonic(),
                    bucket=ring_bucket, tokens=n, rows=ring_bucket,
                    kind="ring",
                    program=program_key("ring_prefill",
                                        bucket=ring_bucket),
                    flops=self._perf.call_flops(n, n))
            else:
                take = min(len(st.todo), self.prefill_chunk)
                bucket = next(b for b in _PREFILL_BUCKETS if b >= take)
                # A padded bucket must not extend past the cache end —
                # dynamic_update_slice would clamp the start and corrupt
                # earlier rows. Shrink the chunk until its bucket fits.
                while st.start + bucket > self.max_len and take > 1:
                    bucket //= 2
                    take = min(take, bucket)
                if st.start + bucket > self.max_len:
                    self._prefilling.pop(0)
                    self._finish(req, "error",
                                 error="KV cache exhausted during "
                                       "prefill")
                    return
                if self.paged and not self._kv_blocks.ensure(
                        slot.index, st.start + bucket):
                    # The rehearsed mid-prefill exhaustion: shed THIS
                    # request with retry_after and exact accounting
                    # (ensure is all-or-nothing), never crash the
                    # engine (kv.block_alloc chaos drill).
                    self._prefilling.pop(0)
                    self._paged_exhausted_finish(
                        req, "KV block pool exhausted during prefill")
                    return
                chunk = st.todo[:take]
                padded = np.zeros((bucket,), np.int32)
                padded[:take] = chunk
                self._sink("prefill", bucket=bucket, tokens=padded,
                           start=st.start, slot=slot.index,
                           last=take - 1)
                # numpy scalars, not jnp ones: each eager jnp scalar is
                # its own device dispatch.
                prog = self._prefill_program(st.start, bucket)
                st.last_logits = self._run_chunk_prefill(
                    slot, padded, st.start, take - 1, bucket)
                slot.tokens.extend(chunk)
                st.start += take
                slot.kv_written = st.start
                st.todo = st.todo[take:]
                # Attribution: one padded-bucket chunk (rows computed =
                # the bucket; useful = the chunk) against the KV
                # horizon it attended. The interval covers dispatch —
                # the device compute overlaps later step records.
                self._tracer.step(
                    "engine_prefill", t0p, time.monotonic(),
                    bucket=bucket, tokens=take, rows=bucket,
                    kind="chunk", program=prog,
                    flops=self._perf.call_flops(take, st.start))
            # Each completed chunk is forward progress — for EVERY
            # request in the prefill FIFO, not just the head: the ones
            # queued behind it are advancing toward service, and
            # counting their wait as "no progress" would let the
            # watchdog force-fail healthy requests behind one long
            # prompt.
            now = time.monotonic()
            for waiting in self._prefilling:
                waiting.req.last_progress_at = now
            if st.todo:
                return  # next chunk on a later iteration
            self._prefilling.pop(0)
            self._m_prefill.observe((time.monotonic() - st.t0) * 1000)
            if req.params.prefill_only:
                # Disaggregated prefill tier: the prompt's KV is
                # written — park it to the host pool and finish
                # WITHOUT sampling or activating (zero decode-slot
                # occupancy; the router migrates the parked entry to
                # a decode replica, router/disagg.py).
                self._prefill_park_finish(req, slot)
                return
            if req.fsm is not None:
                # Masked first-token sample from the FSM start state;
                # also activates — _st_sample_place defers the fetch
                # like the plain path below.
                self._activate(req, slot)
                self._st_sample_place(req, slot, st.last_logits)
                return
            cfg_row = np.array([slot.index, req.params.temperature,
                                req.params.top_k, req.params.top_p],
                               np.float32)
            self._sink("sample_place", cfg_row=cfg_row)
            first, self._cur_tokens, self._rng_dev = \
                self._get_sample_place_fn()(
                    st.last_logits, self._cur_tokens, self._rng_dev,
                    self._arg(cfg_row))
            self._activate(req, slot)
            self._defer_first(first, [(0, slot.index, req)])
        except Exception as e:
            log.error(f"prefill failed for {req.request_id}: {e}",
                      exc_info=True)
            if self.call_sink is not None:
                # A dispatch error AFTER a published descriptor means
                # per-host device state may have diverged: scoping the
                # error to one request would serve a corrupted cluster.
                # Abort followers and escalate (engine thread →
                # _abort_all; multi-host recovery = cluster restart).
                self._sink("abort", reason=str(e))
                raise
            if self._prefilling and self._prefilling[0] is st:
                self._prefilling.pop(0)
            self._finish(req, "error", error=str(e))

    # Intra-batch sharing engages only when the common prefix is at
    # least this long: below it, the extra prefill wave + copy
    # dispatches cost more than the recompute they save (a share has to
    # move the delta into a SMALLER prefill bucket to win).
    _INTRA_SHARE_MIN = 64

    def _prefill_batched_shared(
            self, batch: list[tuple[_Request, Slot, int, list[int]]]) -> None:
        """Intra-batch shared prefix: when several FRESH admissions of
        one burst share a long leading prefix (a fleet of sessions with
        one system prompt arriving together), prefill the longest-
        prompt leader in a first wave, stamp the shared rows onto the
        other slots by device copy, and batch-prefill only their
        deltas — burst prefill compute drops from N×full toward
        1×full + N×delta."""
        from fasttalk_tpu.engine.slots import _lcp

        fresh = [item for item in batch if item[2] == 0]
        members: list[tuple[tuple, int]] = []
        if len(fresh) >= 2:
            leader = max(fresh, key=lambda it: len(it[0].prompt_tokens))
            lp = leader[0].prompt_tokens
            for item in fresh:
                if item is leader:
                    continue
                pt = item[0].prompt_tokens
                share = _lcp(lp, pt, min(len(lp), len(pt) - 1))
                share -= share % self._STAMP_GRANULE
                if share < self._INTRA_SHARE_MIN:
                    continue
                # Sharing must actually shrink the member's prefill
                # bucket (else two serialized waves + copies are
                # strictly slower than the one batched wave), and the
                # delta bucket must still fit the cache at its new
                # start (the admission guard checked start=0; a clamped
                # out-of-range write start would silently corrupt KV).
                full_b = next(b for b in _PREFILL_BUCKETS
                              if b >= len(pt))
                delta_b = next(b for b in _PREFILL_BUCKETS
                               if b >= max(1, len(pt) - share))
                if delta_b < full_b and share + delta_b <= self.max_len:
                    members.append((item, share))
        if not members:
            self._prefill_batched(batch)
            return
        member_ids = {id(it) for it, _ in members}
        self._prefill_batched([it for it in batch
                               if id(it) not in member_ids])
        lreq, lslot = leader[0], leader[1]
        second: list[tuple[_Request, Slot, int, list[int]]] = []
        for (req, slot, _reused, _todo), share in members:
            if req.finished:
                continue
            # Re-clamp against what the leader actually wrote (its
            # prefill may have errored and finished the request) — and
            # re-check the delta-bucket fit, since a SMALLER share
            # means a LARGER delta whose bucket may no longer fit at
            # the new start.
            share = min(share, lslot.kv_written)
            share -= share % self._STAMP_GRANULE
            delta_b = next(
                (b for b in _PREFILL_BUCKETS
                 if b >= max(1, len(req.prompt_tokens) - share)), None)
            if lreq.finished or share < self._INTRA_SHARE_MIN \
                    or delta_b is None \
                    or share + delta_b > self.max_len:
                second.append((req, slot, 0, req.prompt_tokens))
                continue
            share = self._stamp_prefix(lslot.index, slot.index, share)
            slot.tokens = list(req.prompt_tokens[:share])
            slot.kv_written = share
            self._m_shared.inc(share)
            second.append((req, slot, share, req.prompt_tokens[share:]))
        if second:
            self._prefill_batched(second)

    def _prefill_batched(
            self, batch: list[tuple[_Request, Slot, int, list[int]]]) -> None:
        """Prefill several single-bucket prompts in one device call per
        (bucket, group-size) shape: gather the target slots' KV rows,
        run one batched forward, scatter the rows back, then sample every
        first token in a single batched call."""
        t0 = time.monotonic()
        by_bucket: dict[int, list] = {}
        for item in batch:
            bucket = next(b for b in _PREFILL_BUCKETS
                          if b >= max(1, len(item[3])))
            by_bucket.setdefault(bucket, []).append(item)
        for bucket, group in sorted(by_bucket.items()):
            while group:
                sub, group = group[:self.num_slots], group[self.num_slots:]
                try:
                    self._prefill_group(bucket, sub)
                except Exception as e:
                    log.error(f"batched prefill failed: {e}", exc_info=True)
                    if self.call_sink is not None:
                        # See _advance_prefill: a post-publish dispatch
                        # error must abort the cluster, not be scoped.
                        self._sink("abort", reason=str(e))
                        raise
                    # Scoped to this device call: requests in other
                    # groups (possibly already activated and streaming)
                    # are untouched.
                    for req, _, _, _ in sub:
                        self._finish(req, "error", error=str(e))
        self._m_prefill.observe((time.monotonic() - t0) * 1000)

    def _prefill_group(self, bucket: int,
                       sub: list[tuple[_Request, Slot, int, list[int]]],
                       ) -> None:
        """One batched prefill device call + one batched first-token
        sample for a same-bucket group of requests."""
        if _fp.enabled:
            # Same seam name as the chunked path: _prefill_batched's
            # handler scopes an `error` to this group's requests.
            _fp.fire("engine.prefill.dispatch",
                     request_id=";".join(r.request_id
                                         for r, _, _, _ in sub))
        if self.paged:
            # Blocks for every row's padded write horizon, before any
            # array is built: a row the pool cannot hold sheds HERE
            # with retry_after (exact accounting — ensure is
            # all-or-nothing) and the rest of the group proceeds.
            kept = []
            for item in sub:
                if self._kv_blocks.ensure(item[1].index,
                                          item[2] + bucket):
                    kept.append(item)
                else:
                    self._paged_exhausted_finish(
                        item[0], "KV block pool exhausted during "
                                 "batched prefill")
            sub = kept
            if not sub:
                return
        g = len(sub)
        # Only two group shapes ever compile per bucket: 1 and num_slots.
        # A mid-size burst pads to the full batch (the padded rows are
        # masked) — wasted FLOPs are bounded and tiny next to the cost of
        # compiling per burst size.
        gp = 1 if g == 1 else self.num_slots
        tokens = np.zeros((gp, bucket), np.int32)
        rowcfg = np.zeros((gp, 7), np.float32)
        # Padding rows scatter out of range (mode="drop"); each gets a
        # distinct index so unique_indices holds.
        rowcfg[:, 0] = np.arange(self.num_slots,
                                 self.num_slots + gp, dtype=np.float32)
        for j, (req, slot, start, todo) in enumerate(sub):
            tokens[j, :len(todo)] = todo
            rowcfg[j] = (slot.index, start, len(todo) - 1, 1.0,
                         req.params.temperature, req.params.top_k,
                         req.params.top_p)
        # Gather only as much of each slot row as this chunk can touch,
        # rounded to a KV bucket so the shape set stays small.
        need = int(rowcfg[:, 1].max()) + bucket
        ctx = next((b for b in _KV_BUCKETS
                    if b >= need and b <= self.max_len), self.max_len)
        self._sink("batched_prefill", bucket=bucket, gp=gp, ctx=ctx,
                   tokens=tokens, rowcfg=rowcfg)
        # First tokens stay on device: the program scatters them into
        # the decode chain's current-token vector, and the host copy is
        # async — the engine thread dispatches the first decode call
        # without waiting for the round trip; text is emitted when the
        # fetch lands.
        t0p = time.monotonic()
        if self.paged:
            read_idx = np.zeros((gp, ctx), np.int32)
            write_idx = np.zeros((gp, bucket), np.int32)
            for j in range(gp):
                if j < len(sub):
                    slot_j, start_j = sub[j][1], sub[j][2]
                    read_idx[j] = self._paged_read_indices(
                        slot_j.index, ctx)
                    write_idx[j] = self._paged_write_indices(
                        slot_j.index, start_j, bucket)
                else:
                    write_idx[j] = self._paged_oob_indices(j, bucket)
            fn = self._get_paged_batched_prefill_fn(bucket, gp, ctx)
            (self.cache, firsts_dev, self._cur_tokens,
             self._rng_dev) = fn(
                self.params, self.cache, self._arg(tokens),
                self._arg(rowcfg), self._arg(read_idx),
                self._arg(write_idx), self._cur_tokens, self._rng_dev)
        else:
            fn = self._get_batched_prefill_fn(bucket, gp, ctx)
            (self.cache, firsts_dev, self._cur_tokens,
             self._rng_dev) = fn(
                self.params, self.cache, self._arg(tokens),
                self._arg(rowcfg), self._cur_tokens, self._rng_dev)
        # Attribution row: the call computed gp × bucket token rows
        # (padding rows + per-row bucket padding included); useful =
        # the real prompt tokens. Interval covers dispatch only — the
        # device compute overlaps the following step records.
        real = sum(len(todo) for _, _, _, todo in sub)
        self._tracer.step(
            "engine_prefill", t0p, time.monotonic(), bucket=bucket,
            tokens=real, rows=gp * bucket, kind="batched", group=g,
            program=program_key(
                "batched_prefill", chunk=bucket, group=gp, ctx=ctx,
                **self._kvq_attrs,
                **({"kv_layout": "paged"} if self.paged else {})),
            flops=self._perf.call_flops(real, ctx))
        entries = []
        for j, (req, slot, start, todo) in enumerate(sub):
            slot.tokens.extend(todo)
            slot.kv_written = start + len(todo)
            self._activate(req, slot)
            entries.append((j, slot.index, req))
        self._defer_first(firsts_dev, entries)

    def _should_dispatch(self) -> bool:
        """Dispatch another K-step call only if some running request can
        still use tokens beyond what in-flight calls already promise it.

        Without this cap the dispatcher runs pipeline_depth calls past
        every generation's end; those stale calls hold the (in-order)
        device queue and the NEXT request's prefill — and therefore its
        first token — waits behind all of them. A length-capped
        generation now finishes with an empty pipeline."""
        if self._pending_firsts and self._running and all(
                req.first_pending for req in self._running.values()):
            # Pure admission burst: EVERY running request is still
            # waiting for its prefill-sampled first token. A decode
            # dispatch now would enter the in-order device stream ahead
            # of the firsts fetch and push first-token latency a whole
            # call's compute later (scripts/profile_ttft.py traces the
            # hop). Hold off; the loop blocks on the fetch and decode
            # follows it. Steady state is untouched — any request past
            # its first token makes this condition false.
            return False
        promised: dict[int, int] = {}
        for _, min_toks, _, snap, _, _, _ in self._inflight:
            for _, req in snap:
                promised[id(req)] = promised.get(id(req), 0) + min_toks
        # A first token whose fetch hasn't landed is not yet counted in
        # req.generated but will be — ignoring it over-dispatches one
        # whole stale call at exact-budget boundaries.
        return any(
            req.params.max_tokens - req.generated
            - (1 if req.first_pending else 0) > promised.get(id(req), 0)
            for req in self._running.values())

    def _activate(self, req: _Request, slot: Slot) -> None:
        """Mark a freshly prefilled slot as decoding. The first sampled
        token is already on the device (scattered into the decode
        chain's current-token vector by the caller); its text is emitted
        by _drain_firsts when the async fetch lands."""
        s = slot.index
        slot.active = True
        req.slot = slot
        req.decode_started_at = time.monotonic()
        req.last_progress_at = req.decode_started_at
        if req.admitted_at is not None:
            self._m_prefill_req.observe(
                (req.decode_started_at - req.admitted_at) * 1000)
            if req.prefill_tokens:
                # Measured prefill throughput → the restore policy's
                # cost model (admission-to-activation covers the same
                # dispatch overheads a restore competes against).
                self._kv_policy.note_prefill(
                    req.prefill_tokens,
                    req.decode_started_at - req.admitted_at)
            if self._tracer.enabled:
                self._tracer.add_span(
                    req.request_id, "prefill", req.admitted_at,
                    req.decode_started_at, slot=s,
                    prompt_tokens=len(req.prompt_tokens))
                self._tracer.set_phase(req.request_id, "decode")
        self._running[s] = req
        if req.fsm_entry is not None:
            # The slot's row into the arena's per-FSM class table —
            # shipped with every constrained decode call.
            self._st_sel[s] = req.fsm_entry.sel
        self._positions[s] = len(slot.tokens)
        self._active_mask[s] = True
        self._temps[s] = req.params.temperature
        self._topks[s] = req.params.top_k
        self._topps[s] = req.params.top_p
        self._reps[s] = req.params.repeat_penalty
        self._press[s] = req.params.presence_penalty
        self._freqs[s] = req.params.frequency_penalty
        self._dirty_slots.add(s)
        if self.spec_draft:
            self._dirty_history[s] = list(slot.tokens)

    def _defer_first(self, firsts_dev: Any, entries: list) -> None:
        """Queue first sampled tokens for emission once their
        device→host copy (started here, on a worker) completes."""
        for _, _, req in entries:
            req.first_pending = True
        self._pending_firsts.append(
            (self._fetch(firsts_dev), entries))

    def _drain_firsts(self, block: bool) -> None:
        """Emit first tokens whose fetch has landed (all of them when
        ``block``). Entry guards mirror _retire_oldest: a request that
        finished (cancel, error) before its first token arrived drops
        it."""
        while self._pending_firsts:
            fut, entries = self._pending_firsts[0]
            if not block and not fut.done():
                return
            self._pending_firsts.popleft()
            self._j_wait0 = time.monotonic()
            arr = fut.result()
            self._j_fetched = time.monotonic()
            for j, s, req in entries:
                req.first_pending = False
                if req.finished or self._running.get(s) is not req:
                    continue
                self._consume_token(req, int(arr[j]))
                self._flush_emit(req)

    def _get_hist_patch_fn(self, row_len: int | None = None):
        """Jitted history-row upload for speculative decoding: rows of
        freshly admitted slots replace their history rows wholesale
        (out-of-range slot indices in the padded batch drop).

        ``row_len`` buckets the HOST-SIDE upload: shipping full
        [S, max_len] rows is 512 KB per admission wave when the
        prompts being uploaded are ~100 tokens. The program pads to
        max_len on device — HBM-local and free next to the host
        transfer it replaces."""
        row_len = self.max_len if row_len is None else row_len
        fn = self._hist_patch_fns.get(row_len)
        if fn is None:
            @partial(jax.jit, donate_argnums=(0,))
            def apply_hist(hist, rows, slots):
                full = jnp.zeros((rows.shape[0], self.max_len),
                                 rows.dtype)
                full = jax.lax.dynamic_update_slice(full, rows, (0, 0))
                return hist.at[slots].set(full, mode="drop",
                                          unique_indices=True)

            self._hist_patch_fns[row_len] = apply_hist
            fn = apply_hist
        return fn

    def _patch_slot_state(self) -> None:
        """Apply dirty host mirrors onto the chained device arrays via
        one jitted program and one packed transfer.

        In-flight calls are untouched — safe because their snapshots
        drop tokens of finished requests at retirement, and a freed
        slot's fresh prefill is ordered after any in-flight garbage
        writes by the donated-cache data dependency (see _admit).
        Every later dispatch sees the patched state. This replaces the
        old flush-the-pipeline-and-reupload on every slot-set change,
        which serialised admission behind up to pipeline_depth decode
        calls."""
        if self._st_dirty:
            # FSM-state resets/refreshes (finish → FREE, arena repack):
            # a separate tiny program so the shared patch executable —
            # and therefore the unconstrained serving path — stays
            # byte-identical to the pre-structured engine.
            packed = np.zeros((self.num_slots, 2), np.float32)
            for s in self._st_dirty:
                packed[s] = (1.0, self._st_global_state(s))
            self._st_dirty.clear()
            self._st_state_dev = self._get_st_patch_fn()(
                self._arg(packed), self._st_state_dev)
        if self.spec_draft and self._dirty_history:
            # Prompt tokens of freshly admitted slots -> device history
            # (one bucketed upload + one program that pads to max_len
            # on device; the sampled tokens appended later are
            # maintained in-program).
            longest = max((len(t) for t in
                           self._dirty_history.values()), default=1)
            rb = min(self.max_len,
                     max(256, 1 << (longest - 1).bit_length()))
            rows = np.zeros((self.num_slots, rb), np.int32)
            slots = np.full((self.num_slots,), self.num_slots, np.int32)
            for i, (s, tokens) in enumerate(self._dirty_history.items()):
                rows[i, :min(len(tokens), rb)] = tokens[:rb]
                slots[i] = s
            self._dirty_history.clear()
            self._sink("hist_patch", rb=rb, rows=rows, slots=slots)
            self._history_dev = self._get_hist_patch_fn(rb)(
                self._history_dev, self._arg(rows), self._arg(slots))
        if not self._dirty_slots:
            return
        packed = np.zeros((self.num_slots, 9), np.float32)
        for s in self._dirty_slots:
            packed[s] = (1.0, self._positions[s], self._active_mask[s],
                         self._temps[s], self._topks[s], self._topps[s],
                         self._reps[s], self._press[s], self._freqs[s])
        self._dirty_slots.clear()
        self._sink("patch", packed=packed)
        (self._counts_dev, self._positions_dev, self._active_dev,
         self._temps_dev, self._topks_dev, self._topps_dev,
         self._reps_dev, self._press_dev, self._freqs_dev) = \
            self._get_patch_fn()(
                self._arg(packed), self._counts_dev, self._positions_dev,
                self._active_dev, self._temps_dev, self._topks_dev,
                self._topps_dev, self._reps_dev, self._press_dev,
                self._freqs_dev)

    def _spec_call_wanted(self) -> bool:
        """Per-call speculative/plain decision. "ngram": always spec.
        "auto": spec while the measured EMA tokens-per-verify clears
        the break-even (a verify block costs ~spec_breakeven plain
        steps); below it, plain calls with a periodic probe so the EMA
        tracks workload shifts — acceptance recovers (templated or
        repetitive text arrives) and auto re-engages within one probe
        period."""
        if self.spec_mode == "ngram":
            return True
        if self._spec_ema >= self.spec_breakeven:
            return True
        self._spec_probe_countdown -= 1
        if self._spec_probe_countdown <= 0:
            self._spec_probe_countdown = self._spec_probe_every
            return True
        return False

    def _dispatch_decode(self) -> None:
        """Launch one K-step decode call; does not wait for results."""
        if _fp.enabled:
            # Chaos seam: an `error` here is a dispatch-path failure —
            # it propagates to _run's crash handler (terminal events
            # for every request, supervisor restart), exactly like a
            # real XLA dispatch fault. Host-side only: the jitted
            # decode program itself is byte-identical with or without
            # fault injection.
            _fp.fire("engine.decode.dispatch")
        worst_adv = self.steps_per_call * (self.spec_draft + 1
                                           if self.spec_draft else 1)
        if self.paged and not self._paged_prepare_decode(worst_adv):
            return  # every running request was shed for blocks
        self._patch_slot_state()
        t_disp = time.monotonic()
        active = list(self._running)
        snapshot = list(self._running.items())
        # Short calls while admissions/prefills are pending or a first
        # token's fetch is still in flight (anything TTFT-critical waits
        # behind the in-order device queue); long calls in steady state
        # (amortise the per-call cache boundary copy).
        steps = (self.steps_burst if len(self._sched) or self._prefilling
                 or any(req.first_pending
                        for req in self._running.values())
                 else self.steps_per_call)
        # Device positions lead the host mirrors by the in-flight calls'
        # maximum advances; size the KV bucket for where the device can
        # be at the END of this call.
        base = int(self._positions[active].max()) \
            + sum(adv for _, _, adv, _, _, _, _ in self._inflight)
        # Constrained slot running → the per-call compat matrix
        # (docs/STRUCTURED.md): speculative calls pause (verify-block
        # masking is unvalidated in v1) and the fsm decode variants
        # carry the per-slot FSM state + union tables. With NO
        # constrained slot this block is untouched and the original
        # executables dispatch — the zero-cost-when-off guarantee.
        st_on = any(r.fsm is not None for r in self._running.values())
        T = self.spec_draft + 1
        if self.spec_draft and not st_on and self._spec_call_wanted():
            # Size the KV bucket by the EMA-EXPECTED advance (+1 block
            # of headroom), not the K*T worst case: worst-case sizing
            # jumped to the next bucket immediately — a mid-stream
            # compile (~0.4 s traced) and doubled attention reads for
            # advances that almost never happen. Underestimates are
            # SAFE: the in-call act gate (pos + T <= kv_len) makes a
            # slot sit out steps that would overflow the bucket, the
            # under-delivery shows up in the retired n_out, and the
            # host's position mirrors re-size the next call.
            exp_adv = int(steps * min(float(T),
                                      max(1.0, self._spec_ema) + 1.0))
            # The bucket must leave at least one FULL verify block of
            # headroom past every slot's worst-case position, or the
            # in-call act gate masks every step and the call makes no
            # progress — with mirrors never advancing, the identical
            # no-op call would be re-dispatched forever (livelock;
            # reachable when T > exp_adv near a bucket edge).
            need = base + max(exp_adv, T)
            if need <= self.max_len:
                kv_len = next((b for b in _KV_BUCKETS
                               if b >= need and b <= self.max_len),
                              self.max_len)
                fn = self._get_spec_decode_fn(kv_len, steps)
                self._sink("spec", kv_len=kv_len, steps=steps)
                (self.cache, self._history_dev, self._counts_dev, toks,
                 self._cur_tokens, self._positions_dev,
                 self._rng_dev) = fn(
                    self.params, self.cache, self._history_dev,
                    self._counts_dev, self._cur_tokens,
                    self._positions_dev, self._active_dev,
                    self._temps_dev, self._topks_dev, self._topps_dev,
                    self._reps_dev, self._press_dev, self._freqs_dev,
                    self._rng_dev, *self._paged_decode_args(kv_len))
                if self.paged:
                    self._paged_leads.append(worst_adv)
                # Promise the EMA-expected tokens, not the minimum:
                # spec calls deliver K..K*T, and promising K made the
                # dispatcher queue up to T× too many calls — a
                # stale-call tail holding the in-order device queue for
                # seconds (traced).
                promise = steps * min(float(T),
                                      max(1.0, self._spec_ema))
                self._inflight.append(
                    (self._fetch(toks), promise,
                     exp_adv, snapshot, t_disp, kv_len,
                     program_key("spec_decode", kv_len=kv_len,
                                 steps=steps)))
                return
        max_pos = base + steps
        kv_len = next((b for b in _KV_BUCKETS
                       if b >= max_pos and b <= self.max_len), self.max_len)
        if self.spec_draft:
            # Auto mode chose plain for this call (or the spec bucket
            # check fell through): keep the draft history fresh so the
            # next probe drafts from current text, not stale history.
            fn = self._get_decode_fn(kv_len, steps, with_history=True,
                                     with_fsm=st_on)
            self._sink("decode", kv_len=kv_len, steps=steps,
                       with_history=True)
            if st_on:
                (self.cache, self._history_dev, self._counts_dev,
                 self._st_state_dev, toks, self._cur_tokens,
                 self._positions_dev, self._rng_dev) = fn(
                    self.params, self.cache, self._history_dev,
                    self._counts_dev, self._st_state_dev,
                    self._cur_tokens, self._positions_dev,
                    self._active_dev, self._temps_dev, self._topks_dev,
                    self._topps_dev, self._reps_dev, self._press_dev,
                    self._freqs_dev, self._rng_dev,
                    self._arg(self._st_sel.copy()),
                    self._st_masks_dev, self._st_cls_dev,
                    self._st_nexts_dev,
                    *self._paged_decode_args(kv_len))
            else:
                (self.cache, self._history_dev, self._counts_dev, toks,
                 self._cur_tokens, self._positions_dev,
                 self._rng_dev) = fn(
                    self.params, self.cache, self._history_dev,
                    self._counts_dev, self._cur_tokens,
                    self._positions_dev, self._active_dev,
                    self._temps_dev, self._topks_dev, self._topps_dev,
                    self._reps_dev, self._press_dev, self._freqs_dev,
                    self._rng_dev, *self._paged_decode_args(kv_len))
            if self.paged:
                self._paged_leads.append(worst_adv)
            self._inflight.append(
                (self._fetch(toks), steps, steps,
                 snapshot, t_disp, kv_len,
                 self._decode_program(kv_len, steps, st_on)))
            return
        fn = self._get_decode_fn(kv_len, steps, with_fsm=st_on)
        self._sink("decode", kv_len=kv_len, steps=steps,
                   with_history=False)
        if st_on:
            (self.cache, self._counts_dev, self._st_state_dev, toks,
             self._cur_tokens, self._positions_dev, self._rng_dev) = fn(
                self.params, self.cache, self._counts_dev,
                self._st_state_dev, self._cur_tokens,
                self._positions_dev, self._active_dev, self._temps_dev,
                self._topks_dev, self._topps_dev, self._reps_dev,
                self._press_dev, self._freqs_dev, self._rng_dev,
                self._arg(self._st_sel.copy()), self._st_masks_dev,
                self._st_cls_dev, self._st_nexts_dev,
                *self._paged_decode_args(kv_len))
        else:
            (self.cache, self._counts_dev, toks, self._cur_tokens,
             self._positions_dev, self._rng_dev) = fn(
                self.params, self.cache, self._counts_dev,
                self._cur_tokens, self._positions_dev, self._active_dev,
                self._temps_dev, self._topks_dev, self._topps_dev,
                self._reps_dev, self._press_dev, self._freqs_dev,
                self._rng_dev, *self._paged_decode_args(kv_len))
        if self.paged:
            self._paged_leads.append(worst_adv)
        # Start the device→host copy NOW on a worker thread: by
        # retirement time it has been in flight for a whole call's
        # compute, and later calls' fetches overlap it (see the
        # _fetch_pool note in __init__).
        self._inflight.append(
            (self._fetch(toks), steps, steps,
             snapshot, t_disp, kv_len,
             self._decode_program(kv_len, steps, st_on)))

    def _retire_oldest(self) -> None:
        """Block on the oldest in-flight call and consume its tokens."""
        (fut, _, _, snapshot, t_disp, kv_len,
         program) = self._inflight.popleft()
        if self.paged and self._paged_leads:
            self._paged_leads.popleft()
        if _fp.enabled:
            # Chaos seam: `hang` here is the wedged-device-call
            # scenario — the heartbeat goes stale and the watchdog
            # must detect it and force_fail the stalled requests.
            _fp.fire("engine.retire.fetch")
        gen_before = {id(req): req.generated for _, req in snapshot} \
            if self._tracer.enabled else {}
        if any(req.first_pending for _, req in snapshot):
            # A request in this call still awaits its first token:
            # emit firsts before any of its decode tokens (the firsts
            # copy was issued earlier and overlaps this call's fetch on
            # the worker pool, so this wait is bounded).
            self._drain_firsts(block=True)
        t0 = time.monotonic()
        res = fut.result()  # sync point
        self._j_wait0 = t0
        self._j_fetched = time.monotonic()
        self._m_step.observe((self._j_fetched - t0) * 1000)
        # The block above gave every pending firsts-copy >= one call's
        # wall time to land: emit whatever arrived NOW. Without this, a
        # request admitted after call N dispatched waits for call N+1's
        # retirement (whose snapshot it is in) — burst admissions saw
        # their first tokens staggered one ~140 ms retirement per
        # admission group (measured: WS-burst p50 TTFT 412 ms engine-side
        # vs 166 ms when all requests land in one group).
        if self._pending_firsts:
            self._drain_firsts(block=False)
        consumed = 0  # tokens actually fed to requests (perf ledger)
        if res.ndim == 3:
            # Speculative call [K, S, T+1]: per row, columns :T are the
            # sampled tokens and column T is n_out; the first n_out
            # tokens are real (accepted drafts + the residual sample).
            # Positions advance one per token, same as plain decode.
            for k in range(res.shape[0]):
                for s, req in snapshot:
                    if req.finished or self._running.get(s) is not req:
                        continue
                    n = int(res[k, s, -1])
                    if n:
                        self._m_spec.observe(n)
                        self._spec_ema = (0.9 * self._spec_ema
                                          + 0.1 * n)
                        # Accept/reject accounting: each verify block
                        # offered spec_draft drafts and accepted n-1.
                        req.spec_accepted += n - 1
                        req.spec_drafted += self.spec_draft
                    for i in range(n):
                        if req.finished \
                                or self._running.get(s) is not req:
                            break
                        self._positions[s] += 1
                        consumed += 1
                        self._consume_token(req, int(res[k, s, i]))
        else:
            for k in range(res.shape[0]):
                for s, req in snapshot:
                    if req.finished or self._running.get(s) is not req:
                        # Request ended earlier in this call, or the
                        # slot was re-admitted to a newer request: drop
                        # the token.
                        continue
                    self._positions[s] += 1
                    consumed += 1
                    self._consume_token(req, int(res[k, s]))
        for _, req in snapshot:
            self._flush_emit(req)
        if self._tracer.enabled:
            # One step record per retired call (process-level row) and
            # one decode_step span per participating request: batch
            # occupancy and slot utilization AT DISPATCH TIME, which is
            # what the device actually computed over. The perf ledger's
            # extras: token rows the fixed shapes computed (all S slots
            # every step; spec calls verify T = draft+1 positions per
            # step), tokens actually consumed, the call's KV bucket and
            # the FLOP estimate both imply.
            t1 = time.monotonic()
            spec = res.ndim == 3
            constrained = sum(1 for _, r in snapshot
                              if r.fsm is not None)
            occupancy = round(len(snapshot) / max(1, self.num_slots), 3)
            rows = int(res.shape[0]) * self.num_slots \
                * (res.shape[2] - 1 if spec else 1)
            # kv_bytes: what this call's attention streamed from HBM —
            # every step reads kv_len rows for all S slots, at the
            # cache's HONEST element size (int8 rows + scales under
            # KV_QUANT=int8, not an assumed bf16). Feeds the ledger's
            # KV-bandwidth-utilisation figure next to MFU.
            self._tracer.step(
                "engine_step", t_disp, t1, steps=int(res.shape[0]),
                batch=len(snapshot), slots=self.num_slots,
                occupancy=occupancy, kind="spec" if spec else "plain",
                program=program,
                tokens=consumed, rows=rows, kv_len=kv_len,
                flops=self._perf.call_flops(consumed, kv_len),
                kv_bytes=int(res.shape[0]) * self._kv_read_rows(
                    snapshot, kv_len) * self._kv_row_bytes,
                # weight_bytes: the weights streamed once per step at
                # their RESIDENT size (bf16 / int8+scales / packed
                # int4+scales) — /perf's bandwidth and FLOP/byte read
                # this instead of assuming a bf16 footprint.
                weight_bytes=(int(res.shape[0])
                              * self._weight_bytes_per_step),
                # Mask-apply attribution (docs/STRUCTURED.md): rows
                # with constrained>0 ran the fsm decode variant — the
                # per-step mask gather/unpack cost is the step-duration
                # delta against constrained-free rows of the same
                # (steps, kv_len) shape in the perf ledger.
                **({"constrained": constrained} if constrained else {}))
            for s, req in snapshot:
                self._tracer.add_span(
                    req.request_id, "decode_step", t_disp, t1,
                    slot=s, batch=len(snapshot), occupancy=occupancy,
                    tokens=req.generated - gen_before.get(id(req), 0),
                    kind="spec" if spec else "plain")

    def _consume_token(self, req: _Request, token_id: int) -> None:
        """Handle one newly sampled token for a request (host side)."""
        if req.cancelled:
            self._finish(req, "cancelled")
            return
        if token_id in self.tokenizer.eos_ids \
                and not req.params.ignore_eos:
            self._finish(req, "stop")
            return
        slot = req.slot
        assert slot is not None and req.detok is not None
        slot.tokens.append(token_id)
        req.generated += 1
        if req.fsm is not None:
            # Host mirror of the on-device FSM advance: one dict-free
            # table lookup per token. The device copy is authoritative
            # inside the scan; this replay is what _finish, the
            # terminal-accept check and jump-forward read.
            req.fsm_state = req.fsm.step(req.fsm_state, token_id)
        now = time.monotonic()
        if req.last_token_at is not None:
            gap_ms = (now - req.last_token_at) * 1000
            self._m_intertok.observe(gap_ms)
            if gap_ms > req.max_gap_ms:
                req.max_gap_ms = gap_ms  # SLO inter-token SLI
        req.last_token_at = now
        if req.first_token_at is None:
            req.first_token_at = now
            self._m_ttft.observe(
                (req.first_token_at - req.submitted_at) * 1000)
            self._tracer.event(req.request_id, "first_token")
        self._m_tokens.inc()
        t_detok = time.monotonic()
        delta = req.detok.push(token_id)
        req.detok_s += time.monotonic() - t_detok
        if delta:
            self._stream_text(req, delta)
        if req.finished:
            return  # stop string hit inside _stream_text
        if req.fsm is not None and req.fsm.is_terminal(req.fsm_state):
            # The FSM reached an accept state with EOS as the only
            # continuation: the document is complete. Finish with
            # "stop" NOW — before the budget check below, so a
            # generation that completes its document on its last
            # budgeted token reports "stop", not "length" — and
            # without spending a decode step on the EOS itself.
            self._finish(req, "stop")
            return
        if req.generated >= req.params.max_tokens:
            self._finish(req, "length")
        elif len(slot.tokens) >= self.usable_len:
            self._finish(req, "length")
        elif req.fsm is not None:
            self._st_note_jump_candidate(req)

    def _stream_text(self, req: _Request, delta: str) -> None:
        """Emit text, holding back any suffix that could start a stop seq."""
        stops = req.params.stop
        req.pending_text += delta
        if not stops:
            req.emit_buf += req.pending_text
            req.pending_text = ""
            return
        for stop in stops:
            idx = req.pending_text.find(stop)
            if idx >= 0:
                req.emit_buf += req.pending_text[:idx]
                req.pending_text = ""
                self._finish(req, "stop", suppress_flush=True)
                return
        hold = 0
        for stop in stops:
            for k in range(min(len(stop) - 1, len(req.pending_text)), 0, -1):
                if req.pending_text.endswith(stop[:k]):
                    hold = max(hold, k)
                    break
        cut = len(req.pending_text) - hold
        emit_now, req.pending_text = req.pending_text[:cut], req.pending_text[cut:]
        if emit_now:
            req.emit_buf += emit_now

    def _finish(self, req: _Request, reason: str, error: str | None = None,
                suppress_flush: bool = False, code: str = "model_error",
                retry_after: float | None = None) -> None:
        # Atomic check-and-set against the watchdog thread's
        # force_fail: without the lock, a request completing at the
        # instant its stall crosses the cancel threshold could get BOTH
        # a success terminal and a "stalled" error, and an ok=False SLO
        # sample for a request that actually finished.
        with self._term_lock:
            if req.finished:
                return
            req.finished = True
        if req.admitted_at is not None:
            # Admission→finish wall time feeds the scheduler's
            # service-time EMA (wait estimates, retry_after hints).
            self._sched.note_service_time(
                time.monotonic() - req.admitted_at)
        if reason != "cancelled":
            # Cancels are the client's choice, not an SLO sample;
            # watchdog-failed requests were already recorded as errors
            # by force_fail (idempotent either way). Queue-deadline
            # expiry and KV block-pool exhaustion are load SHEDDING
            # (utils/errors.ENGINE_SHED_CODES, the same taxonomy the
            # serving layers map to 429/retry_after): counting them as
            # SLO errors would page the error-rate objective for
            # exactly the mechanisms that protect the admitted
            # requests' latency (docs/OBSERVABILITY.md).
            if code in ENGINE_SHED_CODES and error is not None:
                with self._term_lock:
                    already = req.slo_recorded
                    req.slo_recorded = True
                if not already:
                    self._slo.record_shed(req.params.priority)
            else:
                self._record_slo(req, ok=error is None)
        if req.fsm is not None:
            self._st_release(req)
        slot = req.slot
        if slot is not None:
            decoding = self._running.get(slot.index) is req
            slot.active = False
            slot.last_used = time.monotonic()
            self._running.pop(slot.index, None)
            self._active_mask[slot.index] = False
            self._temps[slot.index] = 0.0
            self._reps[slot.index] = 1.0
            self._press[slot.index] = 0.0
            self._freqs[slot.index] = 0.0
            if decoding:
                # KV rows are written only up to the position reached by
                # *feeding* tokens; a final token kept on max_tokens/stop
                # was sampled but never fed — not trusted for reuse.
                # (If the request died before activation, the prefill
                # paths maintained kv_written themselves and the
                # positions mirror is stale — leave it alone.)
                slot.kv_written = min(slot.length,
                                      int(self._positions[slot.index]))
            # Host positions mirror is authoritative again (the device
            # copy may have speculatively advanced past the kept length).
            self._positions[slot.index] = slot.length
            self._dirty_slots.add(slot.index)
            if self.paged and slot.session_id is not None:
                # Reclaim decode-growth slack past the trusted rows.
                # Safe against the still-draining pipeline: its
                # garbage writes land in the freed blocks strictly
                # before any reallocation's writes (in-order dispatch
                # stream, old table captured at dispatch).
                self._kv_blocks.truncate(slot.index, slot.kv_written)
                # Donate the finished turn's clean prefix to the radix
                # tree NOW (kv_written just settled): the next request
                # — any session sharing this prefix, not just this one
                # — inherits the blocks with zero registration. Runs
                # before the deferred release below so the holds land
                # while the table refs still pin the blocks.
                self._radix_insert_slot(slot)
            sid = slot.session_id
            if sid is not None and sid in self._release_after:
                self._release_after.discard(sid)
                self.slots.release_session(sid)
                self._kv_pool.purge(sid)  # deferred release: same rule
        self._by_id.pop(req.request_id, None)

        if not suppress_flush and req.detok is not None \
                and reason not in ("cancelled",):
            req.pending_text += req.detok.flush()
        if req.pending_text and reason != "cancelled":
            # Final flush still honours stop strings (text that was held
            # back may contain one).
            text = req.pending_text
            for stop in req.params.stop:
                idx = text.find(stop)
                if idx >= 0:
                    text = text[:idx]
                    reason = "stop"
            req.emit_buf += text
        req.pending_text = ""
        self._flush_emit(req)

        if self._tracer.enabled:
            now = time.monotonic()
            if req.admitted_at is None:
                # Never admitted (cancelled/errored in the queue): the
                # whole lifetime was queue wait.
                self._tracer.add_span(req.request_id, "queue_wait",
                                      req.submitted_at, now,
                                      summary=True)
            if req.decode_started_at is not None:
                attrs: dict[str, Any] = {
                    "tokens": req.generated, "finish_reason": reason,
                    "prompt_tokens": len(req.prompt_tokens)}
                if req.spec_drafted:
                    attrs["spec_accepted"] = req.spec_accepted
                    attrs["spec_rejected"] = (req.spec_drafted
                                              - req.spec_accepted)
                if req.fsm is not None:
                    attrs["structured"] = True
                    attrs["jump_tokens"] = req.jump_tokens
                self._tracer.add_span(req.request_id, "decode",
                                      req.decode_started_at, now,
                                      summary=True, **attrs)
            if req.detok_s > 0:
                # Aggregate span: total detokenize time, anchored so it
                # ends at finish (per-token spans would be absurdly
                # fine-grained — this keeps the phase visible in the
                # report and the timeline without per-token overhead).
                self._tracer.add_span(req.request_id, "detokenize",
                                      now - req.detok_s, now,
                                      summary=True, aggregate=True)
            self._tracer.set_phase(req.request_id, "finishing")

        if error is not None:
            event = {"type": "error", "error": error, "code": code}
            if retry_after is not None:
                event["retry_after"] = retry_after
            self._emit(req, event)
            return
        duration = time.monotonic() - req.submitted_at
        ttft_ms = ((req.first_token_at or time.monotonic())
                   - req.submitted_at) * 1000
        self._emit(req, {
            "type": "cancelled" if reason == "cancelled" else "done",
            "finish_reason": reason,
            "stats": {
                "tokens_generated": req.generated,
                "processing_time_ms": duration * 1000,
                "tokens_per_second": req.generated / duration
                if duration > 0 else 0.0,
                "ttft_ms": ttft_ms,
                "prompt_tokens": len(req.prompt_tokens),
                # Tokens actually PREFILLED (the delta after resident/
                # restore reuse) — the honest prefill-throughput feed
                # for the fleet's migration policy; prompt_tokens over
                # TTFT would overstate throughput by the cache-hit
                # fraction.
                "prefill_tokens": req.prefill_tokens,
            },
        })

    def _flush_emit(self, req: _Request) -> None:
        """Send the text batched during one retirement as a single token
        event. At full batch this collapses steps_per_call × num_slots
        queue crossings per call into one per request — the host-side
        per-token cost (call_soon_threadsafe + event-loop wakeup) was a
        measurable slice of aggregate throughput."""
        if req.emit_buf:
            text, req.emit_buf = req.emit_buf, ""
            event: dict = {"type": "token", "text": text}
            if req.params.journey:
                # Journey stamps (observability/journey.py): the
                # retirement's fetch-wait start / fetch-landed marks
                # plus the enqueue instant. The serving loop adds its
                # dequeue and ws-write boundaries; out-of-order stamps
                # (a flush from a different retirement than the fetch
                # the marks describe) are clamped forward there.
                event["j"] = {"w": self._j_wait0,
                              "f": self._j_fetched,
                              "e": time.monotonic()}
            self._emit(req, event)

    def _emit(self, req: _Request, event: dict) -> None:
        try:
            req.loop.call_soon_threadsafe(req.out_queue.put_nowait, event)
        except RuntimeError:
            pass  # client loop already closed; drop
