"""Engine construction from service Config.

The device branch the reference routed through _detect_compute_device
(reference: app/utils/config.py:17-60) plus provider selection
(websocket_server_vllm.py:74-138) collapse here into one factory: the
``tpu`` provider builds the in-tree JAX engine on whatever platform JAX
has (TPU in production, CPU in tests); ``fake`` builds the test engine.
"""

from __future__ import annotations

import jax.numpy as jnp

from fasttalk_tpu.engine.engine import EngineBase, TPUEngine
from fasttalk_tpu.engine.fake import FakeEngine
from fasttalk_tpu.engine.tokenizer import load_tokenizer
from fasttalk_tpu.models.configs import get_model_config
from fasttalk_tpu.models.loader import find_checkpoint_dir, load_params
from fasttalk_tpu.utils.config import Config
from fasttalk_tpu.utils.logger import get_logger

log = get_logger("engine.factory")

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


def weight_bytes_by_tier(m, dsize: int, tp: int = 1,
                         group: int = 128) -> dict:
    """Per-device weight bytes for each WEIGHT_QUANT tier — the one
    place the weight-footprint math lives (budget check, overflow
    remedies, BENCH_MODE=int4 envelopes, tests).

    Sharding facts encoded (parallel/sharding.py): norm scales
    replicate; matmuls/embedding shard over "tp"; every quantized
    tensor gains float32 scales, counted replicated (conservative —
    they are KiB-to-half-MiB scale).
    """
    norm_params = (2 * m.num_layers + 1) * m.hidden_size
    # The seven stacked layer matmuls (quantization/int4.py INT4_LEAVES).
    matmul_per_layer = (m.hidden_size * m.q_dim
                        + 2 * m.hidden_size * m.kv_dim
                        + m.q_dim * m.hidden_size
                        + 3 * m.hidden_size * m.intermediate_size)
    scales_per_layer = (m.q_dim + 2 * m.kv_dim + m.hidden_size
                        + 2 * m.intermediate_size + m.hidden_size)
    matmul = m.num_layers * matmul_per_layer
    scales8 = m.num_layers * scales_per_layer
    # Embedding (and untied lm_head) quantize per ROW at int8 in both
    # quantized tiers — the gather and the streaming head kernel want
    # per-row scales (quantization/__init__.py).
    table = m.hidden_size * m.vocab_size
    tscales = m.vocab_size
    if not m.tie_embeddings:
        table += m.hidden_size * m.vocab_size
        tscales += m.vocab_size
    other = m.param_count() - matmul - table - norm_params  # qkv biases
    return {
        "off": ((m.param_count() - norm_params) * dsize // tp
                + norm_params * dsize),
        "int8": ((matmul + table) // tp + other * dsize // tp
                 + (scales8 + tscales) * 4 + norm_params * dsize),
        # int4: two matmul weights per byte + one f32 scale per
        # (group x out-channel); table stays int8 per-row.
        "int4": (matmul // 2 // tp + (matmul // group) * 4
                 + table // tp + tscales * 4
                 + other * dsize // tp + norm_params * dsize),
    }


def _effective_weight_quant(cfg: Config) -> str:
    """The weight tier the build will actually run. Config resolves
    WEIGHT_QUANT and the legacy TPU_QUANTIZE alias at construction,
    but callers that assign ``cfg.quantize`` AFTER construction
    (tests, scripts predating the weight_quant knob) bypass
    __post_init__ — honor the legacy attr the way the pre-int4
    factory did."""
    legacy = "off" if cfg.quantize in ("", "none", "off") else cfg.quantize
    if cfg.weight_quant == "off" and legacy != "off":
        return legacy
    return cfg.weight_quant


def check_hbm_budget(model_cfg, cfg: Config, dtype, n_devices: int) -> dict:
    """Account weights + KV cache against the HBM budget before any
    allocation, so a bad TPU_DECODE_SLOTS / TPU_MAX_MODEL_LEN fails with
    a named message instead of an opaque device OOM mid-load. Wires the
    TPU_HBM_UTILIZATION knob the way the reference never wired its
    VLLM_GPU_MEMORY_UTILIZATION passthrough (reference:
    .env.vllm.example:40 — forwarded to the external container, no
    in-tree accounting).

    Returns the accounting dict (bytes, per device); raises ValueError
    when over budget. The CPU backend exposes no memory stats and skips
    the check (tests); on a TPU a missing ``bytes_limit`` is an error,
    never a skipped check.

    Sharding facts the math encodes (parallel/sharding.py): weights
    shard over "tp" only (each dp replica holds a full copy); the KV
    cache shards over both "tp" (kv heads) and "dp" (slots). Int8
    weights count int8 bytes because quantization happens host-side
    before placement (ops/quant.py quantizing_put) — HBM never holds
    the bf16 copy.
    """
    import jax

    dev = jax.local_devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if not limit and jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{dev} reports no memory_stats()['bytes_limit']: the HBM "
            "budget check cannot run, and serving without it trades a "
            "named startup error for a device OOM mid-load")
    dsize = jnp.dtype(dtype).itemsize
    tp = max(1, cfg.tp_size)
    m = model_cfg
    # The per-tier footprint math lives in weight_bytes_by_tier (norm
    # scales replicated, matmuls/embedding sharded over "tp", f32
    # scales counted replicated).
    weight_quant = _effective_weight_quant(cfg)
    tiers = weight_bytes_by_tier(m, dsize, tp=tp,
                                 group=cfg.weight_quant_group)
    wbytes_dev = tiers.get(weight_quant, tiers["off"])
    if cfg.kv_quant == "int8":
        # Quantized KV tier (ops/kv_quant.py): int8 rows + per-row
        # float32 scales — the accounting sees honest quantized bytes,
        # so the same HBM budget admits ~2x the slots x context.
        from fasttalk_tpu.ops.kv_quant import granule_dim

        g = granule_dim(cfg.kv_quant_granule, m.num_kv_heads)
        kv_row_bytes = 2 * (m.num_kv_heads * m.head_dim * 1 + g * 4)
    else:
        kv_row_bytes = m.num_kv_heads * m.head_dim * 2 * dsize
    kv_row_bytes *= m.num_layers  # one logical token row, all layers
    dense_rows = cfg.decode_slots * cfg.max_model_len
    paged = cfg.kv_layout == "paged"
    pool_blocks = 0
    if paged:
        # Paged tier (kvcache/blocks.py): HBM is accounted by POOL
        # BLOCKS, not slots x max_len. KV_POOL_BLOCKS=0 asks for the
        # dense-equivalent pool, then SHRINKS to what the budget
        # actually holds — this fit-to-budget step is exactly where
        # the paged layout admits mixed-context fleets the dense
        # layout rejects outright.
        pool_blocks = cfg.kv_pool_blocks \
            or dense_rows // cfg.kv_block_size
        kv = pool_blocks * cfg.kv_block_size * kv_row_bytes
    else:
        kv = dense_rows * kv_row_bytes
    acct = {
        "weight_bytes_per_device": wbytes_dev,
        "kv_cache_bytes_per_device": kv // n_devices,
        "hbm_limit_bytes": limit,
        "hbm_utilization": cfg.hbm_util,
        "kv_pool_blocks": pool_blocks,
    }
    if limit:
        budget = limit * cfg.hbm_util
        kv_budget = budget - acct["weight_bytes_per_device"]
        block_bytes = cfg.kv_block_size * kv_row_bytes
        fit_blocks = max(0, int(kv_budget // block_bytes))
        if paged and not cfg.kv_pool_blocks:
            # Auto pool: fit to the budget, floored at one full
            # max_len context (below that nothing long can ever run
            # and the layout cannot help).
            floor = -(-cfg.max_model_len // cfg.kv_block_size)
            if fit_blocks < floor:
                raise ValueError(
                    f"KV_LAYOUT=paged: the HBM budget holds only "
                    f"{fit_blocks} KV blocks of {cfg.kv_block_size} "
                    f"tokens after {wbytes_dev / 2**30:.2f} GiB of "
                    f"weights, below the {floor} blocks one "
                    f"TPU_MAX_MODEL_LEN={cfg.max_model_len} context "
                    "needs. Lower TPU_MAX_MODEL_LEN, enable "
                    "KV_QUANT=int8, or raise TPU_HBM_UTILIZATION.")
            pool_blocks = min(pool_blocks, fit_blocks)
            acct["kv_pool_blocks"] = pool_blocks
            acct["kv_cache_bytes_per_device"] = \
                pool_blocks * block_bytes // n_devices
        need = (acct["weight_bytes_per_device"]
                + acct["kv_cache_bytes_per_device"])
        if need > budget:
            # The blocks-available math, and the remedy that actually
            # changes the admission model — not just smaller numbers
            # for the same dense layout. Always show the weight-bytes
            # math per tier: quartering weight bytes is the other
            # first-order lever, and the reader should see what each
            # tier would cost on THEIR model before retuning KV knobs.
            tier_math = (
                f"Weight bytes/device by tier ("
                f"WEIGHT_QUANT={weight_quant}): "
                f"off(bf16)={tiers['off'] / 2**30:.2f} GiB, "
                f"int8={tiers['int8'] / 2**30:.2f} GiB, "
                f"int4+scales={tiers['int4'] / 2**30:.2f} GiB "
                f"(group={cfg.weight_quant_group}).")
            if paged:
                remedy = (
                    f"Lower KV_POOL_BLOCKS ({pool_blocks}; 0 = "
                    "fit-to-budget), KV_BLOCK_SIZE "
                    f"({cfg.kv_block_size}), or TPU_MAX_MODEL_LEN "
                    f"({cfg.max_model_len}); enable WEIGHT_QUANT=int4 "
                    "/ KV_QUANT=int8; or raise TPU_HBM_UTILIZATION. "
                    + tier_math)
            else:
                dense_blocks = dense_rows // cfg.kv_block_size
                remedy = (
                    f"The dense layout preallocates every slot at "
                    f"worst-case context: TPU_DECODE_SLOTS="
                    f"{cfg.decode_slots} x TPU_MAX_MODEL_LEN="
                    f"{cfg.max_model_len} = {dense_rows} KV rows "
                    f"({dense_blocks} blocks of {cfg.kv_block_size} "
                    f"tokens), but the budget holds only {fit_blocks} "
                    "blocks after weights. Set KV_LAYOUT=paged to "
                    "admit by blocks actually in use (KV_BLOCK_SIZE="
                    f"{cfg.kv_block_size}), or lower TPU_DECODE_SLOTS "
                    "/ TPU_MAX_MODEL_LEN, enable WEIGHT_QUANT=int4 "
                    "(or int8) / KV_QUANT=int8, or raise TPU_TP_SIZE "
                    "to shard over more chips. " + tier_math)
            raise ValueError(
                f"Model + KV cache need {need / 2**30:.2f} GiB/device "
                f"but the HBM budget is {budget / 2**30:.2f} GiB "
                f"({limit / 2**30:.2f} GiB x TPU_HBM_UTILIZATION="
                f"{cfg.hbm_util}). {remedy}")
    return acct


def build_engine(cfg: Config) -> EngineBase:
    if cfg.llm_provider == "fake":  # internal/testing
        return FakeEngine()
    if cfg.llm_provider in ("vllm", "openai"):
        # "openai" = any OpenAI-compatible HTTP backend; same wire
        # protocol as vLLM. (The reference validated 'openai' but had no
        # handler for it — SURVEY.md §5 config notes.)
        from fasttalk_tpu.engine.remote import VLLMRemoteEngine

        return VLLMRemoteEngine(cfg.vllm_base_url, cfg.vllm_model,
                                api_key=cfg.vllm_api_key,
                                timeout_s=cfg.vllm_timeout,
                                max_inflight=cfg.remote_max_inflight,
                                admission_timeout_s=(
                                    cfg.sched_default_deadline_s),
                                connect_retries=(
                                    cfg.remote_connect_retries))
    if cfg.llm_provider == "ollama":
        from fasttalk_tpu.engine.remote import OllamaRemoteEngine

        return OllamaRemoteEngine(cfg.ollama_base_url, cfg.model_name,
                                  keep_alive=cfg.ollama_keep_alive,
                                  timeout_s=cfg.ollama_timeout,
                                  max_inflight=cfg.remote_max_inflight,
                                  admission_timeout_s=(
                                      cfg.sched_default_deadline_s),
                                  connect_retries=(
                                      cfg.remote_connect_retries))
    # Persistent compilation cache before the first compile: warmup's
    # executables reload from disk on repeat starts of the same config.
    from fasttalk_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache(cfg.compile_cache)
    # Multi-host: bring up the JAX distributed runtime (DCN) before any
    # device use so meshes can span every host. No-op outside a
    # configured multi-host environment. Lives here (not in the CLI) so
    # bench, `main.py test`, and library users all inherit it.
    from fasttalk_tpu.parallel.distributed import maybe_initialize

    maybe_initialize()
    # The first backend touch of the process. An explicit COMPUTE_DEVICE
    # that is not available raises ComputeDeviceError here, before any
    # allocation — the engine never comes up on a device it was not
    # asked for.
    import jax

    from fasttalk_tpu.utils.config import (ComputeDeviceError,
                                           detect_compute_device)

    device = detect_compute_device(cfg.compute_device)
    if device == "tpu" and jax.default_backend() != "tpu":
        raise ComputeDeviceError(
            f"compute device resolved to 'tpu' but JAX's default "
            f"backend is {jax.default_backend()!r}: arrays would be "
            f"placed off the chip")
    log.info(f"compute device: {device} (requested "
             f"{cfg.compute_device}); JAX backend "
             f"{jax.default_backend()}, {jax.device_count()} device(s), "
             f"kind {jax.devices()[0].device_kind}")
    model_cfg = get_model_config(cfg.model_name, cfg.model_path)
    dtype = _DTYPES.get(cfg.dtype, jnp.bfloat16)
    acct = check_hbm_budget(model_cfg, cfg, dtype,
                            n_devices=max(1, cfg.tp_size * cfg.dp_size
                                          * cfg.sp_size))
    log.info("HBM budget check passed",
             weight_gib=round(acct["weight_bytes_per_device"] / 2**30, 2),
             kv_gib=round(acct["kv_cache_bytes_per_device"] / 2**30, 2),
             limit_gib=round((acct["hbm_limit_bytes"] or 0) / 2**30, 2))
    mesh = put = raw_put = None
    if cfg.tp_size > 1 or cfg.dp_size > 1 or cfg.sp_size > 1:
        from fasttalk_tpu.parallel.mesh import make_mesh
        from fasttalk_tpu.parallel.sharding import param_put

        mesh = make_mesh(dp=cfg.dp_size, sp=cfg.sp_size,
                         tp=cfg.tp_size)
        # Weights go straight into their TP shards as they stream off
        # disk — a 70B checkpoint must never materialise on one chip.
        put = param_put(mesh, dtype)
        raw_put = param_put(mesh, None)
    weight_quant = _effective_weight_quant(cfg)
    if weight_quant in ("int8", "int4"):
        from fasttalk_tpu.ops.quant import quantizing_put

        if put is None:
            put = lambda arr, path: jax.device_put(jnp.asarray(arr, dtype))  # noqa: E731
            raw_put = lambda arr, path: jax.device_put(jnp.asarray(arr))  # noqa: E731
        # Quantize host-side, tensor by tensor, before placement: device
        # HBM peaks at quantized bytes, not the transient bf16 copy.
        if weight_quant == "int4":
            # quantizing_put_int4 routes embed/lm_head through the int8
            # putter itself — hand it the un-wrapped puts.
            from fasttalk_tpu.quantization.int4 import (quantizing_put_int4,
                                                        validate_group)

            validate_group(model_cfg, cfg.weight_quant_group)
            put = quantizing_put_int4(put, raw_put, cfg.weight_quant_group)
        else:
            put = quantizing_put(put, raw_put)

    ckpt = find_checkpoint_dir(cfg.model_path, model_cfg.name) \
        if cfg.model_path else None
    if ckpt:
        from fasttalk_tpu.models.prepared_cache import (cache_meta,
                                                        load_prepared,
                                                        save_prepared)

        quant = weight_quant
        params = load_prepared(model_cfg, cfg.model_path, dtype, quant,
                               mesh, ckpt_dir=ckpt,
                               group=cfg.weight_quant_group)
        loaded = True
        if params is None:
            params = load_params(model_cfg, ckpt, dtype, put)
            if quant == "int8":
                log.info("Quantized matmul weights to int8 "
                         "(per-channel symmetric, host-side per tensor)")
            elif quant == "int4":
                log.info(
                    "Quantized layer matmuls to int4 (group-wise "
                    f"symmetric, group={cfg.weight_quant_group}, "
                    "data-free scales; run scripts/quantize_checkpoint.py "
                    "for AWQ-calibrated scales — its output lands in the "
                    "same prepared cache this load path reads)")
            # Cache the engine-ready pytree so the next restart skips
            # the whole safetensors->stack->cast->quantize->shard
            # pipeline (best-effort). An AWQ-calibrated cache written by
            # scripts/quantize_checkpoint.py has the same meta and wins
            # by already existing.
            save_prepared(params, cfg.model_path,
                          cache_meta(model_cfg, dtype, quant, mesh,
                                     ckpt_dir=ckpt,
                                     group=cfg.weight_quant_group))
    else:
        # No checkpoint: random init directly on the device(s) — zero
        # host->device weight transfer (models/loader.py).
        from fasttalk_tpu.models.loader import init_params_device

        log.warning(f"No checkpoint for {model_cfg.name!r} under "
                    f"{cfg.model_path!r}; using random-initialised weights")
        params, loaded = init_params_device(
            model_cfg, dtype, mesh=mesh, quantize=weight_quant,
            weight_quant_group=cfg.weight_quant_group), False
    tokenizer = load_tokenizer(cfg.model_path, cfg.model_name,
                               cfg.tokenizer_path,
                               template=model_cfg.chat_template)
    if not loaded and getattr(tokenizer, "vocab_size", 0) <= 512:
        # WEIGHT-FREE serving only (never when real weights loaded — a
        # checkpoint missing its tokenizer.json must not be silently
        # paired with an unrelated vocab): with no checkpoint tokenizer
        # the byte fallback inflates an English prompt ~6x (1
        # token/byte), which pushed weight-free benches into prefill
        # buckets real deployments never hit — burst TTFT then measured
        # tokenizer inflation, not the serving path
        # (scripts/profile_ttft.py). Prefer the bundled real 32k BPE
        # (scripts/make_bench_tokenizer.py) when the model vocab can
        # hold it.
        import os

        from fasttalk_tpu.engine.tokenizer import HFTokenizer

        bundled = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "assets", "bench_tokenizer.json")
        if os.path.isfile(bundled):
            cand = HFTokenizer(bundled, template=model_cfg.chat_template)
            if cand.vocab_size <= model_cfg.vocab_size:
                tokenizer = cand
    log.info(
        f"Building TPU engine: model={model_cfg.name} "
        f"({model_cfg.param_count() / 1e9:.2f}B params, "
        f"weights {'loaded' if loaded else 'random-init'}), "
        f"slots={cfg.decode_slots}, max_len={cfg.max_model_len}, "
        f"dtype={cfg.dtype}, weight_quant={weight_quant}, "
        f"kv_quant={cfg.kv_quant}, kv_layout={cfg.kv_layout}"
        + (f" ({acct['kv_pool_blocks']} x {cfg.kv_block_size}-token "
           f"blocks)" if cfg.kv_layout == "paged" else "")
        + f", mesh={dict(mesh.shape) if mesh else 'single-device'}")
    engine = TPUEngine(
        model_cfg, params, tokenizer,
        num_slots=cfg.decode_slots, max_len=cfg.max_model_len,
        prefill_chunk=cfg.prefill_chunk, dtype=dtype,
        context_window=min(cfg.default_context_window, cfg.max_model_len),
        mesh=mesh, use_pallas_attention=cfg.use_pallas_attention,
        use_pallas_int8=cfg.use_pallas_int8,
        weight_quant=weight_quant,
        weight_quant_group=cfg.weight_quant_group,
        use_pallas_int4=cfg.use_pallas_int4,
        steps_per_call=cfg.decode_steps_per_call,
        pipeline_depth=cfg.pipeline_depth,
        sampling_method=cfg.sampling,
        spec_decode=cfg.spec_decode,
        spec_draft_len=cfg.spec_draft_len,
        spec_breakeven=cfg.spec_breakeven,
        shared_prefix=cfg.shared_prefix,
        queue_bound=cfg.sched_queue_bound,
        default_deadline_s=cfg.sched_default_deadline_s,
        bulk_aging_s=cfg.sched_bulk_aging_s,
        kv_host_budget_mb=cfg.kv_host_budget_mb,
        kv_park_ttl_s=cfg.kv_park_ttl_s,
        kv_park_idle_s=cfg.kv_park_idle_s,
        kv_restore_min_tokens=cfg.kv_restore_min_tokens,
        kv_quant=cfg.kv_quant,
        kv_quant_granule=cfg.kv_quant_granule,
        kv_layout=cfg.kv_layout,
        kv_block_size=cfg.kv_block_size,
        kv_pool_blocks=acct["kv_pool_blocks"],
        kv_reserve_policy=cfg.kv_reserve_policy,
        kv_reserve_tokens=cfg.kv_reserve_tokens,
        kv_radix=cfg.kv_radix_enabled,
        kv_radix_min_blocks=cfg.kv_radix_min_blocks,
        kv_radix_evict_policy=cfg.kv_radix_evict_policy,
        structured=cfg.structured_mode,
        structured_max_states=cfg.structured_max_states,
        structured_state_budget=cfg.structured_state_budget,
        structured_jf_min=cfg.structured_jf_min,
        structured_cache=cfg.structured_cache,
        structured_json_depth=cfg.structured_json_depth)
    return engine
