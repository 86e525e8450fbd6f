"""Benchmark: streamed output tokens/sec END TO END over WebSocket.

Measures the BASELINE north-star metric — WebSocket output tok/s and
p50 TTFT for Llama-3.2-1B, 1 and N concurrent sessions — by starting
the REAL server (WebSocketLLMServer on aiohttp, the same app
`main.py websocket` serves) and driving N `ws://` clients through the
full JSON protocol on loopback. Every counted token crossed a real
WebSocket (VERDICT r2 asked exactly this; the r2 bench stopped at the
engine's async seam).

``BENCH_MODE=engine`` falls back to the engine-seam measurement
(no sockets) for isolating engine regressions.

``BENCH_MODE=fleet`` runs the router scale-out scenario
(docs/ROUTER.md): N in-process CPU replicas behind a FleetRouter behind
the real WS server vs a single replica with the same per-replica slot
count — aggregate tok/s measures what scaling out buys — then kills the
most-loaded replica mid-stream and reports failover-resume latency
(every affected stream must see a ``resumed`` frame, never an error).
It then runs the session-fabric pair: (1) drain-migrate vs
drain-release follow-up TTFT on long parked sessions (cross-replica KV
migration must beat re-prefill), and (2) a rolling restart of N
replicas under live streams (drain → kill → restart each in turn) with
zero client-visible error frames — only ``resumed`` events. Finally the
disaggregation pair (docs/ROUTER.md "Disaggregated prefill/decode"): a
mid-decode long-prompt burst against a role-split fleet (prefill tier
hands finished KV to the decode tier over the migration wire) vs a
mixed control — role-split must protect decode inter-token p99 with
TTFT inside the priced-migration budget and zero error frames.

``BENCH_MODE=disagg`` runs only that disaggregation pair and prints
the decode ITL p99 gain (role-split over mixed) as its headline.

``BENCH_MODE=longctx`` runs the quantized-KV capacity scenario
(docs/KVCACHE.md "Quantized tier"): long-context sessions parked into
a FIXED ``KV_HOST_BUDGET_MB``, int8 KV (``KV_QUANT=int8``) vs the bf16
control in subprocess-isolated phases — reports parked-session
capacity per budget (headline: the ratio, expected ~2x), restore-
latency p50 both ways, and decode tok/s (must stay within noise).

``BENCH_MODE=paged`` runs the paged-KV capacity scenario
(docs/KVCACHE.md "Paged tier"): a mixed-context fleet on a FIXED
KV-row budget, dense layout (admission priced at slots x max_len) vs
paged block tables (priced at blocks in use) in subprocess-isolated
phases — reports peak concurrent sessions per layout (headline: the
ratio), the same-slot-count short-context decode tok/s pair (the
gather/scatter overhead bound, target within 10%), and aliased-prefix
HBM savings.

``BENCH_MODE=radix`` runs the automatic-prefix-cache scenario
(docs/KVCACHE.md "Automatic prefix cache"): a multi-turn agent
workload that re-submits its growing transcript every turn under a
FRESH session id (the stateless-proxy pattern — same-session resident
reuse can never serve it), radix on (``KV_RADIX_ENABLED=true``) vs
off in subprocess-isolated phases — reports follow-up-turn TTFT both
ways (headline: the speedup, acceptance >= 2x), the tree's hit rate
and bytes saved.

``BENCH_MODE=roofline`` runs the measured-vs-ceiling attribution sweep
(docs/ROOFLINE.md): every decode configuration the compat matrix
serves — (kv_quant x kv_layout x kernel) cells from
``BENCH_RF_CONFIGS`` crossed with the ``BENCH_RF_STEPS``
steps-per-call/fetch-cadence axis — each in its own subprocess at full
slot occupancy, reporting tok/s NEXT TO the perf ledger's
decomposition (device-busy/host-gap fractions, MFU, KV + weight read
bandwidth, and the first-order HBM ceiling fraction).

``BENCH_MODE=int4`` runs the weight-tier capacity scenario
(docs/QUANTIZATION.md): a FIXED device-HBM budget (default 1.5x the
bf16 weight footprint, ``BENCH_I4_BUDGET_MB`` to override) priced per
tier with the SAME math the factory's admission check uses
(engine/factory.py weight_bytes_by_tier) — the headline is the
resident sessions x context envelope ratio (int4+scales vs bf16,
expected >= 2x: whatever the weights stop eating, the KV cache gets) —
plus measured decode tok/s per tier (off/int8/int4) in
subprocess-isolated phases (int4 must stay within noise of int8: both
stream the same dequant-fused matmul shape).

``BENCH_MODE=structured`` runs the constrained-decoding scenario
(docs/STRUCTURED.md): per-step mask-apply overhead vs an unconstrained
control (target <5% tok/s), and jump-forward's forced-token fraction +
e2e delta on a forced-chain-heavy schema, greedy, engine-seam.

``BENCH_MODE=chaos`` runs the recovery-path scenario
(docs/RESILIENCE.md): (1) a failpoints-off control — off vs
armed-but-inert (p=0 rule on the decode dispatch seam) must agree
within 1% tok/s, proving FAULT_POINTS-unset costs nothing; (2)
engine-restart MTTR p50 over injected crash_thread drills
(crash-detected -> supervised restart -> first post-restart token);
(3) router failover resume-latency p50 (kill a replica mid-decode
under a FakeEngine fleet — the routing layer's recovery deadline).

``BENCH_MODE=profiler`` runs the continuous-profiler overhead control
(docs/OBSERVABILITY.md "Continuous profiler and program attribution"):
decode tok/s with the host stack sampler off vs on at ``PROF_HZ``,
pairwise-interleaved like the chaos failpoints control — the headline
is the median on/off delta (target |delta| < 1%), reported next to the
host-gap cause decomposition and per-program attribution the ON
phases produced.

``BENCH_MODE=overload`` runs the admission-control scenario
(docs/SCHEDULING.md): an OPEN-LOOP arrival process (one request every
``BENCH_ARRIVAL_MS`` ms for ``BENCH_OVERLOAD_S`` s, regardless of
completions — the regime where the r1 unbounded queue grew without
bound) against a bounded scheduler, reporting shed rate, expiry rate,
max observed queue depth vs the bound, and admitted-request queue-wait
p50/p95/p99. The headline value is GOODPUT: streamed tokens/s of
admitted requests while the excess is being shed with retry_after.

Weights are random-init (no checkpoint in the image): compute cost is
identical to real weights, which is what throughput measures.

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": "tok/s", "vs_baseline": N}
vs_baseline compares against the reference's published ~150 tok/s for
llama3.2:1b on an RTX 3090 (reference: README.md:474, BASELINE.md).
"""

from __future__ import annotations

import asyncio
import json
import os
import statistics
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def slo_goodput_summary() -> tuple[float | None, str]:
    """(lifetime goodput of the interactive class or None, alert
    state) from the process SLO engine — the bench's 'did the admitted
    requests actually meet the promise' number."""
    from fasttalk_tpu.observability.slo import get_slo

    cls = get_slo().snapshot()["classes"].get("interactive", {})
    return ((cls.get("totals") or {}).get("goodput"),
            cls.get("alert", "ok"))


def fmt_goodput(goodput: float | None) -> str:
    return "n/a" if goodput is None else f"{goodput:.1%}"


def reset_slo_after_warmup() -> None:
    """Warmup requests ate XLA compiles; their blown latencies are not
    the steady state the goodput headline claims."""
    from fasttalk_tpu.observability.slo import reset_slo

    reset_slo()


def perf_attribution() -> dict | None:
    """Step-ledger digest (observability/perf.py) over the measured
    window: occupancy, padding-waste fraction, wall-time decomposition
    and MFU next to the tok/s headline, so BENCH_*.json says not just
    how fast but WHERE the remaining time went. None when the engine
    recorded no step telemetry (tracing disabled / remote provider)."""
    from fasttalk_tpu.observability.perf import get_perf

    s = get_perf().summary()
    return s if s.get("device_busy_frac") is not None else None


def _child_env(**overrides: str) -> dict:
    """Environment for a bench subprocess phase. Children log at
    WARNING unless the caller pinned LOG_LEVEL themselves: child
    stderr lands in the captured bench tail, and per-connection INFO
    lines from a warmed engine were drowning the summary lines the
    tail exists for."""
    env = dict(os.environ)
    env.setdefault("LOG_LEVEL", "WARNING")
    env.update(overrides)
    return env


BASELINE_TOKS = 150.0  # reference llama3.2:1b on RTX 3090 (README.md:474)
# Env overrides are for smoke-testing on CPU; the driver runs defaults.
MODEL = os.environ.get("BENCH_MODEL", "llama3.2:1b")
NUM_SESSIONS = int(os.environ.get("BENCH_SESSIONS", "16"))
MAX_TOKENS = int(os.environ.get("BENCH_MAX_TOKENS", "128"))
MODE = os.environ.get("BENCH_MODE", "ws")
PORT = int(os.environ.get("BENCH_PORT", "18613"))
# Fixed-length generations for TRAINED checkpoints (e.g.
# BENCH_MODEL=tinychat MODEL_PATH=fasttalk_tpu/assets
# BENCH_IGNORE_EOS=1): a trained model answers the bench prompt with a
# short reply + EOS, which measures nothing; ignore_eos decodes the
# full budget. Irrelevant for random-init weights (EOS ~never sampled).
IGNORE_EOS = os.environ.get("BENCH_IGNORE_EOS", "") == "1"
PROMPT = ("You are a concise assistant for a realtime voice app. "
          "Explain, in plain language, how a systolic array multiplies "
          "matrices and why that favours large batched matmuls.")


# ---------------- engine-seam mode (legacy) ----------------

async def run_session(engine, i: int, max_tokens: int) -> dict:
    from fasttalk_tpu.engine.engine import GenerationParams

    t0 = time.monotonic()
    ttft = None
    tokens = 0
    params = GenerationParams(temperature=0.7, top_k=40, top_p=0.9,
                              max_tokens=max_tokens)
    messages = [{"role": "user", "content": f"[session {i}] {PROMPT}"}]
    async for event in engine.generate(f"bench-req-{i}", f"bench-sess-{i}",
                                       messages, params):
        if event["type"] == "token":
            if ttft is None:
                ttft = (time.monotonic() - t0) * 1000.0
        elif event["type"] == "done":
            tokens = event["stats"]["tokens_generated"]
        elif event["type"] == "error":
            raise RuntimeError(f"generation failed: {event}")
    return {"tokens": tokens, "ttft_ms": ttft or 0.0,
            "wall_s": time.monotonic() - t0}


# ---------------- WebSocket mode (the real metric) ----------------

async def ws_session(http, i: int, max_tokens: int) -> dict:
    """One full protocol exchange; counts tokens that crossed the wire."""
    t0 = time.monotonic()
    ttft = None
    tokens = 0
    reported = 0
    async with http.ws_connect(f"ws://127.0.0.1:{PORT}/ws/llm") as ws:
        msg = json.loads((await ws.receive()).data)
        assert msg["type"] == "session_started", msg
        await ws.send_json({"type": "start_session",
                            "config": {"temperature": 0.7, "top_k": 40,
                                       "top_p": 0.9,
                                       "max_tokens": max_tokens,
                                       "ignore_eos": IGNORE_EOS}})
        msg = json.loads((await ws.receive()).data)
        assert msg["type"] == "session_configured", msg
        t0 = time.monotonic()
        await ws.send_json({"type": "user_message",
                            "text": f"[session {i}] {PROMPT}"})
        while True:
            frame = await ws.receive()
            msg = json.loads(frame.data)
            if msg["type"] == "token":
                if ttft is None:
                    ttft = (time.monotonic() - t0) * 1000.0
                tokens += 1
            elif msg["type"] == "response_complete":
                reported = msg["stats"]["tokens_generated"]
                break
            elif msg["type"] == "error":
                raise RuntimeError(f"generation failed: {msg}")
        await ws.send_json({"type": "end_session"})
        await ws.receive()  # session_ended
    return {"tokens": reported or tokens, "ttft_ms": ttft or 0.0,
            "wall_s": time.monotonic() - t0}


async def bench_ws(cfg) -> dict:
    import aiohttp
    from aiohttp import web

    from fasttalk_tpu.engine.factory import build_engine
    from fasttalk_tpu.serving.launcher import build_agent
    from fasttalk_tpu.serving.server import WebSocketLLMServer

    t0 = time.monotonic()
    engine = build_engine(cfg)
    log(f"engine built in {time.monotonic() - t0:.1f}s; warming up...")
    t1 = time.monotonic()
    engine.warmup(cfg.warmup)
    engine.start()
    log(f"warmup done in {time.monotonic() - t1:.1f}s")
    server = WebSocketLLMServer(cfg, engine, build_agent(cfg, engine))
    runner = web.AppRunner(server.app)
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", PORT).start()
    log(f"server up on :{PORT} "
        f"(engine+warmup {time.monotonic() - t0:.1f}s total)")

    try:
        async with aiohttp.ClientSession() as http:
            # Warmup traffic: compile every shape the measurement hits
            # (single path AND the full-batch burst path).
            log("protocol warmup...")
            t2 = time.monotonic()
            await ws_session(http, 990, 8)
            await asyncio.gather(*(ws_session(http, 900 + i, 8)
                                   for i in range(NUM_SESSIONS)))
            log(f"protocol warmup done in {time.monotonic() - t2:.1f}s")
            reset_slo_after_warmup()

            # Median of 3 measurement passes per phase, after one
            # warmup pass: one pass is one sample. Run-to-run spread
            # on a local chip has not been measured yet (ROADMAP S1).
            singles = []
            for rep in range(3):
                s = await ws_session(http, 100 + rep, MAX_TOKENS)
                singles.append((s["tokens"] / s["wall_s"], s["ttft_ms"]))
                log(f"  1 session (pass {rep + 1}): "
                    f"{singles[-1][0]:.1f} tok/s, "
                    f"TTFT {singles[-1][1]:.0f}ms")
            single_tps = statistics.median(t for t, _ in singles)
            single_ttft = statistics.median(t for _, t in singles)

            aggs = []
            for rep in range(3):
                await asyncio.sleep(1)  # drain stale pipeline tails
                t3 = time.monotonic()
                results = await asyncio.gather(
                    *(ws_session(http, 1000 * rep + i, MAX_TOKENS)
                      for i in range(NUM_SESSIONS)))
                wall = time.monotonic() - t3
                total_tokens = sum(r["tokens"] for r in results)
                aggs.append((total_tokens / wall, statistics.median(
                    r["ttft_ms"] for r in results)))
                log(f"  {NUM_SESSIONS} sessions (pass {rep + 1}): "
                    f"{total_tokens} tok in {wall:.2f}s = "
                    f"{aggs[-1][0]:.1f} tok/s aggregate, "
                    f"p50 TTFT {aggs[-1][1]:.0f}ms")
            agg_tps = statistics.median(a for a, _ in aggs)
            p50_ttft = statistics.median(t for _, t in aggs)
            if os.environ.get("BENCH_DUMP_METRICS"):
                from fasttalk_tpu.utils.metrics import get_metrics

                d = get_metrics().to_dict()
                for k in ("engine_prefill_ms", "engine_decode_wait_ms",
                          "engine_ttft_ms"):
                    log(f"  METRIC {k}: {d.get(k)}")
    finally:
        await runner.cleanup()
        engine.shutdown()

    return {"single_tps": single_tps, "single_ttft_ms": single_ttft,
            "agg_tps": agg_tps, "p50_ttft_ms": p50_ttft}


# ---------------- multiturn mode (KV host-offload tier) ----------------

async def _mt_turn(engine, i: int, messages: list[dict],
                   max_tokens: int) -> tuple[str, float]:
    """One engine-seam turn; returns (reply text, TTFT ms)."""
    from fasttalk_tpu.engine.engine import GenerationParams

    t0 = time.monotonic()
    ttft = None
    text = ""
    params = GenerationParams(temperature=0.7, top_k=40, top_p=0.9,
                              max_tokens=max_tokens)
    async for ev in engine.generate(
            f"mt-{i}-{len(messages)}", f"mt-sess-{i}", messages, params):
        if ev["type"] == "token":
            if ttft is None:
                ttft = (time.monotonic() - t0) * 1000.0
            text += ev["text"]
        elif ev["type"] == "error":
            raise RuntimeError(f"generation failed: {ev}")
    return text, ttft or 0.0


async def _mt_phase(cfg, sessions: int, turns: int,
                    max_tokens: int) -> dict:
    """One full multiturn scenario against a freshly built engine:
    ``sessions`` concurrent sessions each running ``turns`` turns under
    slot pressure (slots < sessions, so every wave evicts residents).
    Reports follow-up-turn (turn >= 2) TTFT and the pool's stats."""
    from fasttalk_tpu.engine.factory import build_engine

    engine = build_engine(cfg)
    engine.warmup(cfg.warmup)
    engine.start()
    followup_ttfts: list[float] = []
    try:
        histories: list[list[dict]] = [
            [{"role": "user", "content": f"[session {i}] {PROMPT}"}]
            for i in range(sessions)]
        # Warmup wave compiles the prefill/decode shapes the
        # measurement hits, on session ids outside the measured set.
        await asyncio.gather(*(
            _mt_turn(engine, 10_000 + i,
                     [{"role": "user", "content": f"[warm {i}] hi"}], 8)
            for i in range(sessions)))
        for i in range(sessions):
            engine.release_session(f"mt-sess-{10_000 + i}")
        reset_slo_after_warmup()
        for turn in range(turns):
            results = await asyncio.gather(*(
                _mt_turn(engine, i, histories[i], max_tokens)
                for i in range(sessions)))
            for i, (text, ttft) in enumerate(results):
                if turn >= 1:
                    followup_ttfts.append(ttft)
                histories[i].append({"role": "assistant", "content": text})
                histories[i].append(
                    {"role": "user",
                     "content": f"Continue, please (turn {turn + 2})."})
        kv = engine.get_stats().get("kv_host", {})
    finally:
        engine.shutdown()
    followup_ttfts.sort()
    n = len(followup_ttfts)
    return {
        "followup_turns": n,
        "followup_ttft_ms": {
            "p50": round(statistics.median(followup_ttfts), 1) if n else None,
            "p95": round(followup_ttfts[min(n - 1, int(0.95 * n))], 1)
            if n else None,
        },
        "restore_hit_ratio": kv.get("restore_hit_ratio"),
        "restored_total": kv.get("restored_total", 0),
        "parked_total": kv.get("parked_total", 0),
    }


def _mt_run_phase_subprocess(budget_mb: float) -> dict:
    """Run one multiturn phase in a CHILD process: two engines (one
    per phase) in a single process trip an XLA-CPU teardown crash that
    predates this bench mode, and per-phase processes are better
    isolation for a comparison anyway (fresh compile caches, no
    leaked-state asymmetry between the phases)."""
    import subprocess

    env = _child_env(BENCH_MT_PHASE="1",
                     BENCH_KV_BUDGET_MB=str(budget_mb))
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, stdout=subprocess.PIPE, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"multiturn phase (budget {budget_mb} MB) exited "
            f"{proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_multiturn() -> dict:
    """The KV host-offload scenario (docs/KVCACHE.md): N sessions x M
    turns with fewer slots than sessions, so every follow-up turn
    returns to an evicted session — measured twice, with the host pool
    off (KV_HOST_BUDGET_MB=0: follow-ups re-prefill their history) and
    on (follow-ups restore + delta-prefill). Each phase runs in its
    own subprocess."""
    sessions = int(os.environ.get("BENCH_MT_SESSIONS",
                                  str(NUM_SESSIONS)))
    turns = int(os.environ.get("BENCH_MT_TURNS", "3"))
    budget_mb = float(os.environ.get("BENCH_KV_BUDGET_MB", "256"))

    log(f"multiturn: {sessions} sessions x {turns} turns, "
        f"slots < sessions, pool off vs {budget_mb:.0f} MB...")
    log("--- phase 1/2: pool OFF (re-prefill path) ---")
    off = _mt_run_phase_subprocess(0.0)
    log(f"  off: follow-up TTFT p50/p95 "
        f"{off['followup_ttft_ms']['p50']}/"
        f"{off['followup_ttft_ms']['p95']} ms")
    log("--- phase 2/2: pool ON (park/restore path) ---")
    on = _mt_run_phase_subprocess(budget_mb)
    log(f"  on:  follow-up TTFT p50/p95 "
        f"{on['followup_ttft_ms']['p50']}/"
        f"{on['followup_ttft_ms']['p95']} ms, restore hit ratio "
        f"{on['restore_hit_ratio']}")
    speedup = None
    if off["followup_ttft_ms"]["p50"] and on["followup_ttft_ms"]["p50"]:
        speedup = round(off["followup_ttft_ms"]["p50"]
                        / on["followup_ttft_ms"]["p50"], 2)
    return {"sessions": sessions, "turns": turns,
            "kv_budget_mb": budget_mb, "off": off, "on": on,
            "followup_ttft_p50_speedup": speedup}


# ---------------- longctx mode (int8 KV-cache tier) ----------------

def _lc_long_prompt(eng, i: int, target: int) -> str:
    """A per-session-unique prompt calibrated to ~``target`` chat-
    template tokens on the engine's own tokenizer (the leading session
    tag keeps cross-session shared-prefix/intra-batch sharing out of
    the measurement)."""
    base = f"[session {i}] Summarise the following log. "
    filler = ("The quick brown fox jumps over the lazy dog and keeps "
              "running through the quiet valley at a steady pace. ")

    def toks(txt: str) -> int:
        return len(eng.tokenizer.apply_chat_template(
            [{"role": "user", "content": txt}]))

    n0 = toks(base + filler)
    per = max(1, toks(base + filler * 2) - n0)
    reps = 1 + max(0, (target - n0) // per)
    return base + filler * reps


async def _lc_phase(cfg, sessions: int, ctx_tokens: int,
                    max_tokens: int) -> dict:
    """One long-context capacity scenario against a freshly built
    engine: N sessions (N >> slots) each prefill a ~ctx_tokens prompt,
    get evicted and parked into the FIXED host budget; then every
    session returns for a follow-up (restore where its entry survived
    the budget). Reports how many sessions the budget actually held,
    the per-session parked bytes, restore latency, and decode tok/s —
    the int8-KV phase must hold ~2x the sessions and restore in ~half
    the time of the bf16 control on the SAME budget."""
    from fasttalk_tpu.engine.factory import build_engine
    from fasttalk_tpu.utils.metrics import get_metrics

    engine = build_engine(cfg)
    engine.warmup(cfg.warmup)
    engine.start()
    try:
        # Park wave: sequential admissions under slot pressure — each
        # new session evicts (and parks) an older one. Sequential on
        # purpose: batched admissions would interleave evictions and
        # blur the park accounting.
        prompts = [_lc_long_prompt(engine, i, ctx_tokens)
                   for i in range(sessions)]
        for i in range(sessions):
            r = await run_session_msgs(
                engine, f"lc-{i}", f"lc-sess-{i}",
                [{"role": "user", "content": prompts[i]}], max_tokens)
            assert r["tokens"] > 0
        # Let the copy thread drain (parks are async D2H fetches).
        pool = engine._kv_pool
        for _ in range(100):
            st = pool.stats()
            await asyncio.sleep(0.05)
            if pool.stats() == st:
                break
        st = pool.stats()
        entries = pool.snapshot()
        per_session = max((e["bytes"] for e in entries), default=0)
        # Force the restore decision for the latency measurement: on
        # fast-prefill setups (tiny CPU models) the cost model may
        # legitimately refuse bf16 restores — which is itself the
        # break-even shift the int8 tier buys, but this scenario must
        # measure the restore PATH both ways, so bias the EMAs until
        # every surviving entry restores.
        for _ in range(8):
            engine._kv_policy.note_copy(1 << 30, 0.001)
            engine._kv_policy.note_prefill(1, 1.0)
        # Restore wave: every session returns with its history + a
        # follow-up; sessions whose entries survived the budget restore
        # (half-the-bytes H2D on the int8 phase), the evicted ones
        # re-prefill. Most-recently-parked first: each admission parks
        # the occupant it evicts, and walking oldest-first would let
        # that churn LRU-evict every surviving entry moments before
        # its own turn — measuring pool thrash instead of restores.
        ttfts = []
        for i in reversed(range(sessions)):
            msgs = [{"role": "user", "content": prompts[i]},
                    {"role": "assistant", "content": "noted."},
                    {"role": "user", "content": "Continue, please."}]
            r = await run_session_msgs(engine, f"lc2-{i}",
                                       f"lc-sess-{i}", msgs, max_tokens)
            ttfts.append(r["ttft_ms"])
        st2 = engine.get_stats()["kv_host"]
        rh = get_metrics().histogram("kv_restore_ms")
        # Decode throughput check: a full batch of fresh short
        # sessions decoding concurrently — "within noise or better"
        # is the acceptance bar for the quantized phase.
        t0 = time.monotonic()
        results = await asyncio.gather(*(
            run_session_msgs(
                engine, f"lcd-{i}", f"lcd-sess-{i}",
                [{"role": "user", "content": f"[d{i}] {PROMPT}"}], 64)
            for i in range(cfg.decode_slots)))
        wall = time.monotonic() - t0
        tok_s = sum(r["tokens"] for r in results) / wall
        ttfts.sort()
    finally:
        engine.shutdown()
    return {
        "kv_quant": cfg.kv_quant,
        "budget_mb": cfg.kv_host_budget_mb,
        "parked_sessions": st["sessions"],
        "per_session_bytes": per_session,
        "per_session_mb": round(per_session / 2**20, 3),
        "park_rejected": st.get("rejected_total", 0),
        "restored_total": st2["restored_total"],
        "restore_p50_ms": round(rh.percentile(50), 2)
        if st2["restored_total"] else None,
        "followup_ttft_p50_ms": round(
            statistics.median(ttfts), 1) if ttfts else None,
        "decode_tok_s": round(tok_s, 2),
    }


async def run_session_msgs(engine, rid: str, sid: str,
                           messages: list[dict],
                           max_tokens: int) -> dict:
    """Engine-seam turn with explicit messages (longctx helper)."""
    from fasttalk_tpu.engine.engine import GenerationParams

    t0 = time.monotonic()
    ttft = None
    tokens = 0
    params = GenerationParams(temperature=0.7, top_k=40, top_p=0.9,
                              max_tokens=max_tokens)
    async for event in engine.generate(rid, sid, messages, params):
        if event["type"] == "token":
            if ttft is None:
                ttft = (time.monotonic() - t0) * 1000.0
            tokens += len(event["text"])
        elif event["type"] == "done":
            tokens = event["stats"]["tokens_generated"]
        elif event["type"] == "error":
            raise RuntimeError(f"generation failed: {event}")
    return {"tokens": tokens, "ttft_ms": ttft or 0.0,
            "wall_s": time.monotonic() - t0}


def _lc_run_phase_subprocess(kv_quant: str) -> dict:
    """One longctx phase per child process (same isolation rationale as
    multiturn: two warmed engines in one process trip the XLA-CPU
    teardown crash, and fresh processes keep the comparison fair)."""
    import subprocess

    env = _child_env(BENCH_LC_PHASE=kv_quant)
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, stdout=subprocess.PIPE, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"longctx phase (kv_quant={kv_quant}) exited "
            f"{proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_longctx() -> dict:
    """The quantized-KV capacity scenario (docs/KVCACHE.md "Quantized
    tier"): long-context sessions parked into a FIXED KV_HOST_BUDGET_MB,
    int8 KV vs the bf16 control — parked-session capacity per budget,
    restore-latency p50 both ways, and decode tok/s (must be within
    noise or better)."""
    ctx = int(os.environ.get("BENCH_LC_CTX", "384"))
    sessions = int(os.environ.get("BENCH_LC_SESSIONS", "8"))
    # The children size the budget (main()'s BENCH_LC_PHASE branch:
    # the same env gives both the same default) and report it; this
    # parent stays off the model code, which imports jax.
    bucket = 1 << (ctx + 96 - 1).bit_length()
    log(f"longctx: {sessions} sessions x ~{ctx} ctx tokens, bucket "
        f"{bucket}, bf16 vs int8 KV on one fixed host budget...")
    log("--- phase 1/2: bf16 KV (control) ---")
    off = _lc_run_phase_subprocess("none")
    log(f"  bf16 (budget {off['budget_mb']:.1f} MB): "
        f"{off['parked_sessions']} parked x "
        f"{off['per_session_mb']} MB, restore p50 "
        f"{off['restore_p50_ms']} ms, decode {off['decode_tok_s']} "
        f"tok/s")
    log("--- phase 2/2: int8 KV ---")
    on = _lc_run_phase_subprocess("int8")
    log(f"  int8: {on['parked_sessions']} parked x "
        f"{on['per_session_mb']} MB, restore p50 "
        f"{on['restore_p50_ms']} ms, decode {on['decode_tok_s']} "
        f"tok/s")
    budget_mb = off["budget_mb"]
    if on["budget_mb"] != budget_mb:
        raise RuntimeError(f"longctx phases ran on different budgets: "
                           f"{budget_mb} vs {on['budget_mb']} MB")
    cap_ratio = (round(on["parked_sessions"]
                       / off["parked_sessions"], 2)
                 if off["parked_sessions"] else None)
    restore_speedup = (round(off["restore_p50_ms"]
                             / on["restore_p50_ms"], 2)
                       if off["restore_p50_ms"] and on["restore_p50_ms"]
                       else None)
    tok_ratio = (round(on["decode_tok_s"] / off["decode_tok_s"], 3)
                 if off["decode_tok_s"] else None)
    return {"sessions": sessions, "ctx_tokens": ctx, "bucket": bucket,
            "budget_mb": budget_mb, "bf16": off, "int8": on,
            "parked_capacity_ratio": cap_ratio,
            "restore_p50_speedup": restore_speedup,
            "decode_tok_s_ratio": tok_ratio}


# ---------------- int4 mode (weight-tier capacity) ----------------

async def _i4_phase(cfg, max_tokens: int) -> dict:
    """One weight-tier phase against a freshly built engine: a warmup
    decode wave (XLA compile), then a measured full-batch decode wave.
    Reports the tier's RESIDENT weight bytes (what admission prices),
    the per-step STREAMED bytes (what the perf ledger records), and
    decode tok/s."""
    import jax

    from fasttalk_tpu.engine.factory import build_engine

    engine = build_engine(cfg)
    engine.warmup(cfg.warmup)
    engine.start()
    try:
        resident = int(sum(x.nbytes for x in
                           jax.tree_util.tree_leaves(engine.params)))

        async def wave(tag: str) -> float:
            t0 = time.monotonic()
            results = await asyncio.gather(*(
                run_session_msgs(
                    engine, f"i4-{tag}-{i}", f"i4-{tag}-sess-{i}",
                    [{"role": "user", "content": f"[{tag}{i}] {PROMPT}"}],
                    max_tokens)
                for i in range(cfg.decode_slots)))
            wall = time.monotonic() - t0
            return sum(r["tokens"] for r in results) / wall

        await wave("warm")
        tok_s = await wave("run")
    finally:
        engine.shutdown()
    return {
        "weight_quant": cfg.weight_quant,
        "resident_weight_bytes": resident,
        "resident_weight_mb": round(resident / 2**20, 3),
        "streamed_bytes_per_step": engine._weight_bytes_per_step,
        "decode_tok_s": round(tok_s, 2),
    }


def _i4_run_phase_subprocess(tier: str) -> dict:
    """One tier per child process (same isolation rationale as
    multiturn/longctx: two warmed engines in one process trip the
    XLA-CPU teardown crash, and fresh processes keep the tiers fair)."""
    import subprocess

    env = _child_env(BENCH_I4_PHASE=tier)
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, stdout=subprocess.PIPE, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"int4 phase (weight_quant={tier}) exited "
            f"{proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_int4() -> dict:
    """The weight-tier capacity scenario (docs/QUANTIZATION.md): price
    a FIXED device-HBM budget per tier with the factory's own
    admission math, then measure decode tok/s per tier in isolated
    child processes. The envelope is analytic ON PURPOSE — it is the
    exact formula check_hbm_budget admits sessions by, so the headline
    is the serving capacity the factory will actually grant, not a
    simulation of it."""
    phases = {}
    for i, tier in enumerate(("off", "int8", "int4")):
        log(f"--- phase {i + 1}/3: WEIGHT_QUANT={tier} ---")
        phases[tier] = _i4_run_phase_subprocess(tier)
        log(f"  {tier}: {phases[tier]['resident_weight_mb']} MB "
            f"resident, decode {phases[tier]['decode_tok_s']} tok/s")
    # The analytic part imports the factory (and with it jax), so it
    # runs only now that every child has exited and released the chip.
    from fasttalk_tpu.engine.factory import weight_bytes_by_tier
    from fasttalk_tpu.models.configs import get_model_config

    m = get_model_config(MODEL, os.environ.get("MODEL_PATH"))
    group = int(os.environ.get("WEIGHT_QUANT_GROUP", "128"))
    dsize = 2  # bf16 serving dtype
    tiers = weight_bytes_by_tier(m, dsize, tp=1, group=group)
    budget = float(os.environ.get(
        "BENCH_I4_BUDGET_MB",
        str(round(1.5 * tiers["off"] / 2**20, 3)))) * 2**20
    # bf16 KV bytes per resident token (K+V): the KV tier is held
    # fixed so the envelope isolates what the WEIGHT tier frees.
    kv_row = 2 * m.num_layers * m.num_kv_heads * m.head_dim * dsize
    envelope = {t: max(0, int(budget) - b) // kv_row
                for t, b in tiers.items()}
    log(f"int4: fixed HBM budget {budget / 2**20:.1f} MB, weight "
        f"bytes off={tiers['off'] / 2**20:.1f} / "
        f"int8={tiers['int8'] / 2**20:.1f} / "
        f"int4={tiers['int4'] / 2**20:.1f} MB (group {group}) -> "
        f"resident KV envelope {envelope['off']} / {envelope['int8']}"
        f" / {envelope['int4']} token-rows")
    cap_ratio = (round(envelope["int4"] / envelope["off"], 2)
                 if envelope["off"] else None)
    tok_vs_int8 = (round(phases["int4"]["decode_tok_s"]
                         / phases["int8"]["decode_tok_s"], 3)
                   if phases["int8"]["decode_tok_s"] else None)
    return {"budget_mb": round(budget / 2**20, 3), "group": group,
            "weight_bytes": tiers, "kv_row_bytes": kv_row,
            "envelope_token_rows": envelope,
            "envelope_ratio_int4_vs_bf16": cap_ratio,
            "off": phases["off"], "int8": phases["int8"],
            "int4": phases["int4"],
            "decode_tok_s_int4_vs_int8": tok_vs_int8}


# ---------------- paged mode (block-table KV cache) ----------------

async def _pg_session(engine, rid: str, sid: str, messages: list[dict],
                      max_tokens: int) -> dict:
    """One admission-wave turn that RETURNS a shed instead of raising:
    block-pool exhaustion rejections (code kv_blocks_exhausted, with
    retry_after) are a measured outcome of this scenario, not a bench
    failure."""
    from fasttalk_tpu.engine.engine import GenerationParams

    tokens = 0
    params = GenerationParams(temperature=0.7, top_k=40, top_p=0.9,
                              max_tokens=max_tokens)
    async for event in engine.generate(rid, sid, messages, params):
        if event["type"] == "token":
            tokens += 1
        elif event["type"] == "done":
            tokens = event["stats"]["tokens_generated"]
        elif event["type"] == "error":
            return {"tokens": tokens, "shed": True,
                    "code": event.get("code")}
    return {"tokens": tokens, "shed": False, "code": None}


async def _pg_admission_phase(cfg, sessions: int, contexts: list[int],
                              max_tokens: int) -> dict:
    """The fixed-HBM-budget admission scenario, one layout per child
    process: a MIXED-context fleet (the 512–32k production mix scaled
    to the bench max_len) submits concurrently and the phase reports
    how many sessions the layout held resident AT ONCE (peak
    concurrent decodes — the dense layout is hard-capped at
    rows_budget / max_len slots however short the prompts are), plus
    sheds, wall time, and — on the paged phase — the block pool's
    aliased-prefix savings from a shared-system-prompt wave."""
    from fasttalk_tpu.engine.factory import build_engine

    engine = build_engine(cfg)
    engine.warmup(cfg.warmup)
    engine.start()
    try:
        prompts = [_lc_long_prompt(engine, i, ctx)
                   for i, ctx in enumerate(contexts)]
        peak = {"running": 0}
        stop = asyncio.Event()

        async def sampler():
            while not stop.is_set():
                st = engine.get_stats()
                peak["running"] = max(peak["running"], st["running"])
                await asyncio.sleep(0.02)

        samp = asyncio.ensure_future(sampler())
        t0 = time.monotonic()
        results = await asyncio.gather(*(
            _pg_session(engine, f"pg-{i}", f"pg-sess-{i}",
                        [{"role": "user", "content": prompts[i]}],
                        max_tokens)
            for i in range(len(contexts))))
        wall = time.monotonic() - t0
        stop.set()
        await samp
        shed = sum(1 for r in results if r["shed"])
        out = {
            "kv_layout": cfg.kv_layout,
            "slots": cfg.decode_slots,
            "sessions": len(contexts),
            "completed": len(contexts) - shed,
            "shed": shed,
            "peak_concurrent": peak["running"],
            "wall_s": round(wall, 2),
            "tokens": sum(r["tokens"] for r in results),
        }
        if cfg.kv_layout == "paged":
            # Aliased-prefix savings: fresh sessions sharing one long
            # system prompt must stamp by refcount aliasing (zero KV
            # row copies beyond the COW tail block).
            sys_prompt = _lc_long_prompt(engine, 999, 256)
            for j in range(3):
                r = await _pg_session(
                    engine, f"pga-{j}", f"pga-sess-{j}",
                    [{"role": "system", "content": sys_prompt},
                     {"role": "user", "content": f"hello #{j}"}],
                    max_tokens)
                assert not r["shed"], r
            bl = engine.get_stats()["kv_blocks"]
            bs = bl["block_size"]
            out["blocks"] = {k: bl[k] for k in
                            ("total", "in_use", "aliased",
                             "alias_events", "cow_copies",
                             "fragmentation")}
            # Rows the aliased blocks would otherwise hold as copies.
            out["alias_saved_rows"] = bl["aliased"] * bs
    finally:
        engine.shutdown()
    return out


async def _pg_tput_phase(cfg, max_tokens: int) -> dict:
    """Short-context decode throughput at IDENTICAL slot count and a
    dense-equivalent pool: isolates the paged gather/scatter overhead
    (acceptance bar: within 10% of the dense control)."""
    from fasttalk_tpu.engine.factory import build_engine

    engine = build_engine(cfg)
    engine.warmup(cfg.warmup)
    engine.start()
    try:
        # Warmup wave compiles the shapes the measurement hits.
        await asyncio.gather(*(
            run_session_msgs(
                engine, f"pgw-{i}", f"pgw-sess-{i}",
                [{"role": "user", "content": f"[w{i}] hi"}], 8)
            for i in range(cfg.decode_slots)))
        t0 = time.monotonic()
        results = await asyncio.gather(*(
            run_session_msgs(
                engine, f"pgt-{i}", f"pgt-sess-{i}",
                [{"role": "user", "content": f"[d{i}] {PROMPT}"}],
                max_tokens)
            for i in range(cfg.decode_slots)))
        wall = time.monotonic() - t0
    finally:
        engine.shutdown()
    return {"kv_layout": cfg.kv_layout,
            "tok_s": round(sum(r["tokens"] for r in results) / wall, 2)}


def _pg_run_phase_subprocess(phase: str, layout: str) -> dict:
    """One paged phase per child process (same isolation rationale as
    multiturn/longctx: two warmed engines in one process trip the
    XLA-CPU teardown crash, and fresh processes keep the layouts'
    compile caches and heap symmetric)."""
    import subprocess

    env = _child_env(BENCH_PG_PHASE=phase, BENCH_PG_LAYOUT=layout)
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, stdout=subprocess.PIPE, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"paged phase ({phase}/{layout}) exited "
                           f"{proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _pg_mixed_contexts(sessions: int, max_len: int) -> list[int]:
    """The production 512–32k context mix scaled into the bench
    max_len: geometric spread from max_len/32 up to max_len/2."""
    lo, hi = max(32, max_len // 32), max_len // 2
    step = (hi / lo) ** (1.0 / max(1, sessions - 1))
    return [min(hi, int(lo * step ** i)) for i in range(sessions)]


def bench_paged() -> dict:
    """The paged-KV capacity scenario (docs/KVCACHE.md "Paged tier"):
    a FIXED KV-row budget serves a mixed-context fleet under both
    layouts — dense affords only budget/max_len slots (admission
    priced at worst-case context), paged holds sessions by blocks in
    use — plus a same-slot-count short-context throughput pair bounding
    the gather/scatter overhead, and the aliased-prefix HBM savings."""
    sessions = int(os.environ.get("BENCH_PG_SESSIONS", "8"))
    max_len = int(os.environ.get("BENCH_PG_MAX_LEN", "2048"))
    rows = int(os.environ.get("BENCH_PG_KV_ROWS", "6144"))
    bs = int(os.environ.get("KV_BLOCK_SIZE", "16"))
    contexts = _pg_mixed_contexts(sessions, max_len)
    dense_slots = max(1, rows // max_len)
    log(f"paged: {sessions} sessions, contexts {contexts} on a fixed "
        f"{rows}-row KV budget (dense affords {dense_slots} x "
        f"{max_len} slots; paged {rows // bs} x {bs}-token blocks)...")
    log("--- phase 1/4: admission, dense control ---")
    d_adm = _pg_run_phase_subprocess("admission", "dense")
    log(f"  dense: peak {d_adm['peak_concurrent']} concurrent, "
        f"{d_adm['completed']}/{d_adm['sessions']} done in "
        f"{d_adm['wall_s']} s")
    log("--- phase 2/4: admission, paged ---")
    p_adm = _pg_run_phase_subprocess("admission", "paged")
    log(f"  paged: peak {p_adm['peak_concurrent']} concurrent, "
        f"{p_adm['completed']}/{p_adm['sessions']} done in "
        f"{p_adm['wall_s']} s, aliased {p_adm['blocks']['aliased']} "
        f"blocks ({p_adm['alias_saved_rows']} rows saved)")
    log("--- phase 3/4: throughput, dense control ---")
    d_tp = _pg_run_phase_subprocess("tput", "dense")
    log("--- phase 4/4: throughput, paged ---")
    p_tp = _pg_run_phase_subprocess("tput", "paged")
    log(f"  decode tok/s dense {d_tp['tok_s']} vs paged "
        f"{p_tp['tok_s']}")
    ratio = (round(p_adm["peak_concurrent"]
                   / d_adm["peak_concurrent"], 2)
             if d_adm["peak_concurrent"] else None)
    tok_ratio = (round(p_tp["tok_s"] / d_tp["tok_s"], 3)
                 if d_tp["tok_s"] else None)
    return {"sessions": sessions, "contexts": contexts,
            "kv_rows_budget": rows, "max_len": max_len,
            "block_size": bs, "dense_slots": dense_slots,
            "admission": {"dense": d_adm, "paged": p_adm},
            "concurrent_ratio": ratio,
            "alias_saved_rows": p_adm["alias_saved_rows"],
            "throughput": {"dense_tok_s": d_tp["tok_s"],
                           "paged_tok_s": p_tp["tok_s"],
                           "ratio": tok_ratio}}


# ---------------- radix mode (automatic prefix cache) ----------------

async def _rx_turn(engine, sid: str, messages: list[dict],
                   max_tokens: int) -> tuple[str, float]:
    """One agent turn under a FRESH session id, released as soon as it
    finishes — the stateless-proxy agent pattern: no session affinity,
    so nothing resident can serve the transcript prefix next turn.
    Returns (reply text, TTFT ms)."""
    from fasttalk_tpu.engine.engine import GenerationParams

    t0 = time.monotonic()
    ttft = None
    text = ""
    params = GenerationParams(temperature=0.7, top_k=40, top_p=0.9,
                              max_tokens=max_tokens)
    async for ev in engine.generate(f"req-{sid}", sid, messages,
                                    params):
        if ev["type"] == "token":
            if ttft is None:
                ttft = (time.monotonic() - t0) * 1000.0
            text += ev["text"]
        elif ev["type"] == "error":
            raise RuntimeError(f"generation failed: {ev}")
    engine.release_session(sid)
    return text, ttft or 0.0


async def _rx_phase(cfg, agents: int, turns: int,
                    max_tokens: int) -> dict:
    """One radix phase: ``agents`` concurrent agent transcripts, each
    re-submitted in full every turn. With the tree on, turn N should
    alias everything up to turn N-1 and prefill only the delta; off,
    every turn re-prefills the whole transcript. Reports follow-up
    (turn >= 2) TTFT and the tree's counters."""
    from fasttalk_tpu.engine.factory import build_engine

    engine = build_engine(cfg)
    engine.warmup(cfg.warmup)
    engine.start()
    followup_ttfts: list[float] = []
    try:
        histories: list[list[dict]] = [
            [{"role": "user", "content": f"[agent {i}] {PROMPT}"}]
            for i in range(agents)]
        # Warmup wave compiles the prefill/decode shapes the
        # measurement hits, on session ids outside the measured set.
        await asyncio.gather(*(
            _rx_turn(engine, f"rxw-{i}",
                     [{"role": "user", "content": f"[warm {i}] hi"}], 8)
            for i in range(agents)))
        reset_slo_after_warmup()
        for turn in range(turns):
            results = await asyncio.gather(*(
                _rx_turn(engine, f"rx-{i}-t{turn}", histories[i],
                         max_tokens)
                for i in range(agents)))
            for i, (text, ttft) in enumerate(results):
                if turn >= 1:
                    followup_ttfts.append(ttft)
                histories[i].append(
                    {"role": "assistant", "content": text})
                histories[i].append(
                    {"role": "user",
                     "content": f"Next step, please (turn "
                                f"{turn + 2})."})
        radix = engine.get_stats().get("kv_radix", {})
    finally:
        engine.shutdown()
    followup_ttfts.sort()
    n = len(followup_ttfts)
    return {
        "followup_turns": n,
        "followup_ttft_ms": {
            "p50": round(statistics.median(followup_ttfts), 1)
            if n else None,
            "p95": round(followup_ttfts[min(n - 1, int(0.95 * n))], 1)
            if n else None,
        },
        "radix": radix,
    }


def _rx_run_phase_subprocess(phase: str) -> dict:
    """One radix phase per child process (same isolation rationale as
    multiturn/longctx: two warmed engines in one process trip the
    XLA-CPU teardown crash, and fresh processes keep the phases'
    compile caches and heap symmetric)."""
    import subprocess

    env = _child_env(BENCH_RX_PHASE=phase)
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, stdout=subprocess.PIPE, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"radix phase ({phase}) exited "
                           f"{proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_radix() -> dict:
    """The automatic-prefix-cache scenario (docs/KVCACHE.md "Automatic
    prefix cache"): a growing agent transcript re-submitted every turn
    under fresh session ids, measured radix off (every turn re-
    prefills the whole history) and on (turn N aliases the cached
    chain and prefills only the delta). Each phase runs in its own
    subprocess."""
    agents = int(os.environ.get("BENCH_RX_AGENTS", "4"))
    turns = int(os.environ.get("BENCH_RX_TURNS", "4"))

    log(f"radix: {agents} agents x {turns} turns, fresh session id "
        f"per turn, KV_RADIX_ENABLED off vs on...")
    log("--- phase 1/2: radix OFF (re-prefill path) ---")
    off = _rx_run_phase_subprocess("off")
    log(f"  off: follow-up TTFT p50/p95 "
        f"{off['followup_ttft_ms']['p50']}/"
        f"{off['followup_ttft_ms']['p95']} ms")
    log("--- phase 2/2: radix ON (alias + delta-prefill path) ---")
    on = _rx_run_phase_subprocess("on")
    rx = on.get("radix", {})
    log(f"  on:  follow-up TTFT p50/p95 "
        f"{on['followup_ttft_ms']['p50']}/"
        f"{on['followup_ttft_ms']['p95']} ms, hit rate "
        f"{rx.get('hit_rate')}, bytes saved {rx.get('bytes_saved')}")
    speedup = None
    if off["followup_ttft_ms"]["p50"] and on["followup_ttft_ms"]["p50"]:
        speedup = round(off["followup_ttft_ms"]["p50"]
                        / on["followup_ttft_ms"]["p50"], 2)
    return {"agents": agents, "turns": turns, "off": off, "on": on,
            "followup_ttft_p50_speedup": speedup,
            "hit_rate": rx.get("hit_rate"),
            "hit_tokens": rx.get("hit_tokens"),
            "bytes_saved": rx.get("bytes_saved")}


# ---------------- roofline mode (decode attribution sweep) -------------

# The sweep grid: every decode configuration the compat matrix serves,
# as kv_quant:kv_layout:kernel triples. Overridable so a TPU run can
# focus (BENCH_RF_CONFIGS=int8:paged:pallas) and the CPU smoke can
# stay short.
_RF_ALL_CONFIGS = ("none:dense:xla,int8:dense:xla,"
                   "none:dense:pallas,int8:dense:pallas,"
                   "none:paged:xla,int8:paged:xla,"
                   "none:paged:pallas,int8:paged:pallas")


async def _rf_phase(cfg, max_tokens: int) -> dict:
    """One roofline cell: decode at full slot occupancy under one
    (kv_quant x kv_layout x kernel x steps_per_call) configuration,
    then read the perf ledger's attribution over the measured window
    so tok/s never travels without its decomposition
    (docs/ROOFLINE.md)."""
    from fasttalk_tpu.engine.factory import build_engine
    from fasttalk_tpu.observability.perf import get_perf

    engine = build_engine(cfg)
    engine.warmup(cfg.warmup)
    engine.start()
    try:
        # Warmup wave compiles the shapes the measurement hits.
        await asyncio.gather(*(
            run_session_msgs(
                engine, f"rfw-{i}", f"rfw-sess-{i}",
                [{"role": "user", "content": f"[w{i}] hi"}], 8)
            for i in range(cfg.decode_slots)))
        t0 = time.monotonic()
        results = await asyncio.gather(*(
            run_session_msgs(
                engine, f"rf-{i}", f"rf-sess-{i}",
                [{"role": "user", "content": f"[d{i}] {PROMPT}"}],
                max_tokens)
            for i in range(cfg.decode_slots)))
        wall = time.monotonic() - t0
        perf = get_perf().summary()
    finally:
        engine.shutdown()
    toks = sum(r["tokens"] for r in results)
    return {"kv_quant": cfg.kv_quant,
            "kv_layout": cfg.kv_layout,
            "kernel": perf.get("attention_kernel"),
            "steps_per_call": cfg.decode_steps_per_call,
            "slots": cfg.decode_slots,
            "tok_s": round(toks / wall, 2),
            "perf": perf}


def _rf_run_phase_subprocess(kv_quant: str, layout: str, kernel: str,
                             steps: int) -> dict:
    """One roofline cell per child process (same isolation rationale
    as every other multi-engine bench mode: fresh XLA state per cell,
    and a fresh perf-ledger window so cells never read each other's
    step records)."""
    import subprocess

    env = _child_env(BENCH_RF_PHASE="1",
                     BENCH_RF_KV=kv_quant,
                     BENCH_RF_LAYOUT=layout,
                     BENCH_RF_KERNEL=kernel,
                     TPU_DECODE_STEPS=str(steps))
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, stdout=subprocess.PIPE, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"roofline cell ({kv_quant}/{layout}/{kernel}/steps="
            f"{steps}) exited {proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_roofline() -> dict:
    """BENCH_MODE=roofline (docs/ROOFLINE.md): the measured-vs-ceiling
    attribution sweep. Each cell of (kv_quant x kv_layout x kernel) x
    steps_per_call runs decode at full occupancy in its own process
    and reports tok/s NEXT TO the perf ledger's decomposition —
    device-busy/host-gap fractions, MFU, KV and weight read bandwidth,
    and the first-order HBM ceiling (frac_of_ceiling == hbm_bw_util).
    The steps_per_call axis is the fetch-cadence axis: one device call
    covers `steps` tokens per slot between host token fetches."""
    steps_list = [int(s) for s in os.environ.get(
        "BENCH_RF_STEPS", "8,32").split(",") if s.strip()]
    configs = [c.strip().split(":") for c in os.environ.get(
        "BENCH_RF_CONFIGS", _RF_ALL_CONFIGS).split(",") if c.strip()]
    rows = []
    n = len(configs) * len(steps_list)
    i = 0
    for kv_quant, layout, kernel in configs:
        for steps in steps_list:
            i += 1
            log(f"--- roofline cell {i}/{n}: kv={kv_quant} "
                f"layout={layout} kernel={kernel} steps={steps} ---")
            r = _rf_run_phase_subprocess(kv_quant, layout, kernel,
                                         steps)
            p = r["perf"]
            ceil = p.get("frac_of_ceiling")
            ceil_txt = ("n/a (no HBM peak for this device kind)"
                        if ceil is None else str(ceil))
            log(f"  {r['tok_s']} tok/s via {r['kernel']} | busy "
                f"{p.get('device_busy_frac')} gap "
                f"{p.get('host_gap_frac')} | mfu {p.get('mfu')} | "
                f"kv {p.get('kv_read_gbps')} GB/s | ceiling frac "
                f"{ceil_txt}")
            rows.append(r)
    best = max(rows, key=lambda r: r["tok_s"])
    return {"rows": rows,
            "best": {k: best[k] for k in
                     ("kv_quant", "kv_layout", "kernel",
                      "steps_per_call", "tok_s")},
            "best_frac_of_ceiling": best["perf"].get(
                "frac_of_ceiling")}


# ---------------- fleet mode (router scale-out) ----------------

async def _fleet_failover(http, router, handles, max_tokens) -> dict:
    """Failover-resume latency scenario: long sessions stream across
    the fleet, the most-loaded replica's engine is shut down mid-stream,
    and every affected session must resume on a survivor (a `resumed`
    frame, then tokens — never an error frame). Reports the kill→resumed
    and kill→next-token latencies of the affected sessions."""
    n = len(handles) * 2
    shared = [dict(tokens=0, resumed_ms=None, next_token_ms=None,
                   error=None, done=False) for _ in range(n)]
    state = {"kill_t": None}

    async def victim(i):
        got = shared[i]
        async with http.ws_connect(
                f"ws://127.0.0.1:{PORT}/ws/llm") as ws:
            json.loads((await ws.receive()).data)  # session_started
            await ws.send_json({
                "type": "start_session",
                "config": {"max_tokens": max_tokens * 4,
                           "ignore_eos": IGNORE_EOS}})
            await ws.receive()  # session_configured
            await ws.send_json({"type": "user_message",
                                "text": f"[failover {i}] {PROMPT}"})
            resumed = False
            while True:
                msg = json.loads((await ws.receive()).data)
                if msg["type"] == "token":
                    got["tokens"] += 1
                    if resumed and got["next_token_ms"] is None \
                            and state["kill_t"] is not None:
                        got["next_token_ms"] = (
                            time.monotonic() - state["kill_t"]) * 1000
                elif msg["type"] == "resumed":
                    resumed = True
                    if state["kill_t"] is not None:
                        got["resumed_ms"] = (
                            time.monotonic() - state["kill_t"]) * 1000
                elif msg["type"] == "response_complete":
                    got["done"] = True
                    return
                elif msg["type"] == "error":
                    got["error"] = msg.get("error")
                    return

    tasks = [asyncio.create_task(victim(i)) for i in range(n)]
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:  # all sessions streaming?
        if all(v["tokens"] >= 2 for v in shared):
            break
        await asyncio.sleep(0.02)
    # Kill the replica carrying the most live streams.
    owners = [h for _, h in router._routes.values()]
    target = max(handles, key=owners.count)
    affected = owners.count(target)
    log(f"  killing {target.replica_id} with {affected} live streams...")
    state["kill_t"] = time.monotonic()
    await asyncio.get_running_loop().run_in_executor(
        None, target.engine.shutdown)
    await asyncio.gather(*tasks)
    # A failed-over stream must leave ONE stitched cross-replica trace
    # retrievable over the wire (docs/OBSERVABILITY.md "Fleet
    # tracing"): router + both replicas' spans, exactly one terminal
    # event however many replicas served the stream.
    from fasttalk_tpu.observability.trace import get_tracer
    stitched = None
    for t in reversed(get_tracer().completed()):
        if any(s.name == "resume" for s in t.spans):
            async with http.get(f"http://127.0.0.1:{PORT}"
                                f"/traces/{t.request_id}") as r:
                if r.status == 200:
                    stitched = (await r.json()).get("stitched")
            break
    errors = [v["error"] for v in shared if v["error"]]
    resumed = sorted(v["resumed_ms"] for v in shared
                     if v["resumed_ms"] is not None)
    next_tok = sorted(v["next_token_ms"] for v in shared
                      if v["next_token_ms"] is not None)
    out = {
        "sessions": n,
        "affected": affected,
        "resumed": len(resumed),
        "errors": len(errors),
        "resume_latency_ms": {
            "p50": round(statistics.median(resumed), 1) if resumed
            else None,
            "max": round(resumed[-1], 1) if resumed else None,
        },
        "next_token_after_kill_ms": {
            "p50": round(statistics.median(next_tok), 1) if next_tok
            else None,
        },
        "stitched_trace": {
            "resumed": stitched["resumed"],
            "terminal_events": stitched["terminal_events"],
            "components": stitched["components"],
            "n_spans": stitched["n_spans"],
        } if stitched is not None else None,
    }
    log(f"  failover: {len(resumed)}/{affected} resumed, "
        f"{len(errors)} errors, resume p50 "
        f"{out['resume_latency_ms']['p50']} ms")
    if stitched is not None:
        log(f"  stitched trace: {stitched['resumed']} resumed / "
            f"{stitched['terminal_events']} terminal across "
            f"components {stitched['components']}")
    return out


async def _fleet_phase(cfg, replicas: int, sessions: int,
                       max_tokens: int) -> dict:
    """One fleet scenario in THIS process: N in-proc replicas behind a
    FleetRouter behind the real WebSocket server; measure aggregate
    WS tok/s, then (fleets only) the failover-resume scenario."""
    import aiohttp
    from aiohttp import web

    from fasttalk_tpu.engine.factory import build_engine
    from fasttalk_tpu.router import FleetRouter, ReplicaHandle
    from fasttalk_tpu.serving.server import WebSocketLLMServer

    handles = []
    for i in range(replicas):
        t0 = time.monotonic()
        eng = build_engine(cfg)
        eng.warmup(cfg.warmup)
        # Tag each replica's spans so the failover scenario's stitched
        # trace attributes hops to the replica that served them.
        eng.set_trace_component(f"inproc-{i}")
        handles.append(ReplicaHandle(f"inproc-{i}", eng))
        log(f"  replica {i} built+warmed in "
            f"{time.monotonic() - t0:.1f}s")
    router = FleetRouter(handles, probe_interval_s=1.0)
    router.start()
    server = WebSocketLLMServer(cfg, router, None)
    runner = web.AppRunner(server.app)
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", PORT).start()
    out: dict = {"replicas": replicas, "sessions": sessions}
    async with aiohttp.ClientSession() as http:
        log("  protocol warmup...")
        await asyncio.gather(*(ws_session(http, 900 + i, 8)
                               for i in range(sessions)))
        reset_slo_after_warmup()
        t0 = time.monotonic()
        results = await asyncio.gather(
            *(ws_session(http, i, max_tokens)
              for i in range(sessions)))
        wall = time.monotonic() - t0
        total = sum(r["tokens"] for r in results)
        out["agg_tps"] = round(total / wall, 2)
        out["p50_ttft_ms"] = round(statistics.median(
            r["ttft_ms"] for r in results), 1)
        log(f"  {replicas} replica(s): {total} tok in {wall:.2f}s = "
            f"{out['agg_tps']} tok/s aggregate")
        if replicas > 1:
            out["failover"] = await _fleet_failover(http, router,
                                                    handles, max_tokens)
    await runner.cleanup()
    # Deliberately NO engine shutdown: multiple warmed XLA-CPU engines
    # in one process trip a pre-existing teardown crash (see the
    # multiturn notes); the child prints its JSON and hard-exits.
    return out


def _fleet_run_phase_subprocess(replicas: int) -> dict:
    """Each fleet size runs in its own child process (fresh XLA state,
    no teardown-order hazards between phases)."""
    import subprocess

    env = _child_env(BENCH_FLEET_PHASE=str(replicas))
    # Two in-proc engines racing the shared persistent XLA compile
    # cache segfault the XLA-CPU client (observed deterministic);
    # disable it for BOTH phases so the comparison stays fair.
    env["TPU_COMPILE_CACHE"] = "off"
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, stdout=subprocess.PIPE, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"fleet phase ({replicas} replicas) exited "
                           f"{proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_fleet(replicas: int, sessions: int, slots: int) -> dict:
    """The scale-out scenario (docs/ROUTER.md): ``sessions`` concurrent
    WS sessions against 1 replica vs ``replicas`` replicas, each
    replica holding ``slots`` decode slots — the single-replica phase
    is slot-starved (sessions > slots), the fleet serves them all
    concurrently, so aggregate tok/s measures what scaling out buys.
    The fleet phase then kills its most-loaded replica mid-stream and
    reports failover-resume latency."""
    import os as _os

    cores = _os.cpu_count() or 1
    log(f"fleet: {sessions} sessions, {slots} slots/replica, "
        f"1 vs {replicas} replicas on {cores} core(s)...")
    if cores < 2:
        # In-proc CPU replicas share the host's cores: on ONE core a
        # compute-bound decode cannot aggregate faster than a single
        # replica (scale-out buys tok/s only with a core/chip per
        # replica) — the fleet's single-host win is then queue-wait/
        # TTFT, which the report carries alongside.
        log("  WARNING: 1 CPU core — fleet aggregate tok/s cannot "
            "exceed single-replica here; watch p50_ttft_speedup")
    log("--- phase 1/2: single replica ---")
    single = _fleet_run_phase_subprocess(1)
    log("--- phase 2/2: fleet ---")
    fleet = _fleet_run_phase_subprocess(replicas)
    speedup = (round(fleet["agg_tps"] / single["agg_tps"], 2)
               if single.get("agg_tps") else None)
    ttft_speedup = (round(single["p50_ttft_ms"] / fleet["p50_ttft_ms"],
                          2)
                    if fleet.get("p50_ttft_ms") else None)
    return {"sessions": sessions, "slots_per_replica": slots,
            "cores": cores, "single": single, "fleet": fleet,
            "agg_tps_speedup": speedup,
            "p50_ttft_speedup": ttft_speedup}


# ---- fleet fabric: migration-vs-reprefill + rolling restart --------

def _fleet_fabric_cfg(slots: int):
    """Two-replica fabric phases share one engine config: KV host pool
    on, fast idle parks, long context for meaningful prefill."""
    from fasttalk_tpu.utils.config import Config

    return Config(llm_provider="tpu", model_name=MODEL,
                  decode_slots=slots, max_model_len=2048,
                  default_context_window=2048, prefill_chunk=512,
                  dtype="bfloat16", port=PORT,
                  monitoring_port=PORT + 1, enable_agent=False,
                  kv_host_budget_mb=256.0, kv_park_idle_s=0.2,
                  kv_restore_min_tokens=32,
                  quantize=os.environ.get("BENCH_QUANTIZE", "int8"))


async def _fleet_migration_phase(cfg, migrate_on: bool,
                                 sessions: int) -> dict:
    """One side of the migration-vs-reprefill comparison, in THIS
    process: N long-context sessions run their first turn on replica 0
    and idle-park there; replica 0 is then drained (rolling-restart
    shape) and every follow-up turn is measured on replica 1. With
    migration ON the drain moves the parked KV, so follow-ups RESTORE;
    OFF reproduces the pre-fabric behaviour (drain releases, follow-ups
    re-prefill the transcript). Follow-up TTFT p50 is the headline."""
    from fasttalk_tpu.engine.engine import GenerationParams
    from fasttalk_tpu.engine.factory import build_engine
    from fasttalk_tpu.router import FleetRouter, ReplicaHandle

    engines = []
    for i in range(2):
        t0 = time.monotonic()
        eng = build_engine(cfg)
        eng.warmup(cfg.warmup)
        engines.append(eng)
        log(f"  replica {i} built+warmed in "
            f"{time.monotonic() - t0:.1f}s")
    handles = [ReplicaHandle(f"inproc-{i}", e)
               for i, e in enumerate(engines)]
    router = FleetRouter(handles, probe_interval_s=1.0,
                         migrate=migrate_on, migrate_timeout_s=60.0)
    router.start()
    long_prompt = " ".join(f"[{i}] {PROMPT}" for i in range(6))
    greedy = dict(temperature=0.0, top_k=1)

    async def turn(rid, sid, messages, max_tokens=24):
        t0 = time.monotonic()
        ttft = None
        text = []
        async for ev in router.generate(
                rid, sid, messages,
                GenerationParams(max_tokens=max_tokens,
                                 ignore_eos=IGNORE_EOS, **greedy)):
            if ev["type"] == "token":
                if ttft is None:
                    ttft = (time.monotonic() - t0) * 1000.0
                text.append(ev.get("text", ""))
            elif ev["type"] == "error":
                raise RuntimeError(f"bench turn failed: {ev}")
        return ttft or 0.0, "".join(text)

    # First turns, all pinned to replica 0 (the one we will drain).
    replies = {}
    for i in range(sessions):
        sid = f"mig-{i}"
        router.affinity.set(sid, "inproc-0")
        _, replies[sid] = await turn(
            f"t1-{i}", sid,
            [{"role": "user", "content": long_prompt}])
    # Wait for the idle parks (KV_PARK_IDLE_S=0.2 + the 1 Hz tick).
    deadline = time.monotonic() + 30
    pool = engines[0]._kv_pool
    while time.monotonic() < deadline and any(
            pool.parked_len(f"mig-{i}") == 0 for i in range(sessions)):
        await asyncio.sleep(0.05)
    parked = sum(1 for i in range(sessions)
                 if pool.parked_len(f"mig-{i}") > 0)
    summary = await asyncio.to_thread(router.drain_replica, "inproc-0")
    log(f"  drained inproc-0: parked={parked} "
        f"migrated_kv={summary['migrated_kv']} "
        f"released={summary['released']}")
    # Follow-up turns: placement now lands on replica 1.
    ttfts = []
    for i in range(sessions):
        sid = f"mig-{i}"
        msgs = [{"role": "user", "content": long_prompt},
                {"role": "assistant", "content": replies[sid]},
                {"role": "user", "content": "and a short follow-up"}]
        ttft, _ = await turn(f"t2-{i}", sid, msgs, max_tokens=8)
        ttfts.append(ttft)
    ttfts.sort()
    restored = engines[1].get_stats()["kv_host"]["restored_total"]
    return {
        "migrate": migrate_on,
        "sessions": sessions,
        "parked_before_drain": parked,
        "migrated_kv": summary["migrated_kv"],
        "released": summary["released"],
        "followups_restored": restored,
        "followup_ttft_ms": {
            "p50": round(statistics.median(ttfts), 1),
            "max": round(ttfts[-1], 1),
        },
        "migration_policy": router.kv_policy.stats(),
    }
    # Deliberately no engine shutdown (see _fleet_phase note); the
    # child prints its JSON and hard-exits.


async def _fleet_rolling_phase(cfg, n_replicas: int,
                               sessions: int) -> dict:
    """The rolling-restart drill, in THIS process: long streams run
    across the fleet while every replica in turn is drained, KILLED
    mid-stream, and REPLACED by a pre-warmed successor through the
    elastic membership hooks (the k8s rolling-update shape: the new
    pod joins, the old one never comes back). Acceptance: zero
    client-visible error frames — affected streams see ``resumed``
    events and finish normally."""
    from fasttalk_tpu.engine.engine import GenerationParams
    from fasttalk_tpu.engine.factory import build_engine
    from fasttalk_tpu.router import FleetRouter, ReplicaHandle

    engines = {}
    spares = []
    for i in range(n_replicas * 2):  # fleet + one successor each
        t0 = time.monotonic()
        eng = build_engine(cfg)
        eng.warmup(cfg.warmup)
        log(f"  engine {i} built+warmed in "
            f"{time.monotonic() - t0:.1f}s")
        if i < n_replicas:
            engines[f"inproc-{i}"] = eng
        else:
            eng.start()  # successors boot warm, ready to join
            spares.append(eng)
    handles = [ReplicaHandle(rid, e, dead_probes=1)
               for rid, e in engines.items()]
    router = FleetRouter(handles, probe_interval_s=0,
                         failover_retries=n_replicas)
    router.start()
    n_streams = n_replicas * 2
    frames = [[] for _ in range(n_streams)]
    greedy = dict(temperature=0.0, top_k=1)

    async def stream(i):
        async for ev in router.generate(
                f"roll-{i}", f"roll-s{i}",
                [{"role": "user", "content": f"[{i}] {PROMPT}"}],
                GenerationParams(max_tokens=1500, ignore_eos=IGNORE_EOS,
                                 **greedy)):
            frames[i].append(ev)

    tasks = [asyncio.create_task(stream(i)) for i in range(n_streams)]
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if all(any(e["type"] == "token" for e in f) for f in frames):
            break
        await asyncio.sleep(0.02)
    rounds = []
    for i in range(n_replicas):
        rid = f"inproc-{i}"
        t0 = time.monotonic()
        await asyncio.to_thread(router.drain_replica, rid)
        await asyncio.to_thread(engines[rid].shutdown)  # hard kill
        router.probe_once()  # dead within one probe (dead_probes=1)
        await asyncio.sleep(0.3)  # let affected streams resume
        successor = ReplicaHandle(f"{rid}-new", spares[i],
                                  dead_probes=1)
        successor.probe_now()
        router.add_replica(successor)
        router.remove_replica(rid)
        rounds.append({
            "replica": rid, "successor": successor.replica_id,
            "round_s": round(time.monotonic() - t0, 2),
            "successor_state": successor.state,
        })
        log(f"  rolled {rid} -> {successor.replica_id} "
            f"({successor.state}) in {rounds[-1]['round_s']}s")
    await asyncio.gather(*tasks)
    errors = sum(1 for f in frames
                 for e in f if e["type"] == "error")
    resumed = sum(1 for f in frames
                  for e in f if e["type"] == "resumed")
    completed = sum(1 for f in frames if f and f[-1]["type"] == "done")
    return {
        "replicas": n_replicas,
        "streams": n_streams,
        "rounds": rounds,
        "error_frames": errors,
        "resumed_events": resumed,
        "completed": completed,
        "migrations": router.fleet_stats()["counters"]["migrations"],
    }


async def _fleet_disagg_phase(cfg, role_split: bool,
                              sessions: int) -> dict:
    """One side of the disaggregation comparison, in THIS process:
    decode streams hold their slots and stream tokens while
    ``sessions`` long-prompt requests arrive mid-decode. Role-split
    runs replica 0 as the prefill tier (deep queue, zero decode slots)
    and replica 1 as the decode tier — long prompts prefill on 0, hand
    their KV over the migration wire, and decode on 1 — so a decode
    step never sits behind a long prefill chunk in its own scheduler.
    The mixed control runs the SAME engines with no roles, so long
    prefills time-share with decoding slots. Decode inter-token p99 is
    the headline — the number disaggregation exists to protect
    (docs/ROUTER.md "Disaggregated prefill/decode")."""
    from dataclasses import replace as dc_replace

    from fasttalk_tpu.engine.engine import GenerationParams
    from fasttalk_tpu.engine.factory import build_engine
    from fasttalk_tpu.router import FleetRouter, ReplicaHandle

    roles = ("prefill", "decode") if role_split else ("mixed", "mixed")
    engines = []
    for i, role in enumerate(roles):
        # Mirror build_fleet: the prefill tier absorbs burst arrivals
        # in queue depth instead of slot pressure.
        ecfg = (dc_replace(cfg,
                           sched_queue_bound=4 * cfg.sched_queue_bound)
                if role == "prefill" else cfg)
        t0 = time.monotonic()
        eng = build_engine(ecfg)
        eng.warmup(ecfg.warmup)
        engines.append(eng)
        log(f"  replica {i} ({role}) built+warmed in "
            f"{time.monotonic() - t0:.1f}s")
    handles = [ReplicaHandle(f"inproc-{i}", e, role=r)
               for i, (e, r) in enumerate(zip(engines, roles))]
    router = FleetRouter(handles, probe_interval_s=1.0, migrate=True,
                         migrate_timeout_s=60.0,
                         disagg_prefill_min_tokens=128)
    router.start()
    # Long enough to clear the 128-token threshold under BOTH the
    # byte tokenizer (~1 token/char) and a BPE one (~4 chars/token).
    long_prompt = " ".join(f"[{i}] {PROMPT}" for i in range(9))
    greedy = dict(temperature=0.0, top_k=1)
    # Leave decode headroom for the handed-off long sessions so both
    # sides queue comparably; the decode streams are the ITL probes.
    # Their prompt must stay WELL below the handoff threshold in any
    # tokenization, or the probes would take the handoff themselves.
    n_decode = max(1, cfg.decode_slots // 2)
    stamps = [[] for _ in range(n_decode)]
    errors = []

    async def decode_stream(i):
        async for ev in router.generate(
                f"dec-{i}", f"dec-s{i}",
                [{"role": "user", "content": f"[{i}] Say more."}],
                GenerationParams(max_tokens=512, ignore_eos=IGNORE_EOS,
                                 **greedy)):
            if ev["type"] == "token":
                stamps[i].append(time.monotonic())
            elif ev["type"] == "error":
                errors.append(ev)

    async def long_turn(i):
        t0 = time.monotonic()
        ttft = None
        async for ev in router.generate(
                f"long-{i}", f"long-s{i}",
                [{"role": "user", "content": f"[{i}] {long_prompt}"}],
                GenerationParams(max_tokens=16, ignore_eos=IGNORE_EOS,
                                 **greedy)):
            if ev["type"] == "token" and ttft is None:
                ttft = (time.monotonic() - t0) * 1000.0
            elif ev["type"] == "error":
                errors.append(ev)
        return ttft

    dec_tasks = [asyncio.create_task(decode_stream(i))
                 for i in range(n_decode)]
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if all(s for s in stamps):
            break  # every ITL probe is decoding before the burst
        await asyncio.sleep(0.02)
    burst0 = time.monotonic()
    ttfts = await asyncio.gather(*[long_turn(i)
                                   for i in range(sessions)])
    burst1 = time.monotonic()
    for i in range(n_decode):  # probes outlived the burst — done
        router.cancel(f"dec-{i}")
    await asyncio.gather(*dec_tasks)

    # ITL only while the burst was in flight — that is the window the
    # split protects; before/after it both fleets decode undisturbed.
    gaps = sorted(g for s in stamps
                  for a, b in zip(s, s[1:])
                  if b >= burst0 and a <= burst1
                  for g in ((b - a) * 1000.0,))
    if not gaps:  # probes finished early: fall back to the full run
        gaps = sorted((b - a) * 1000.0 for s in stamps
                      for a, b in zip(s, s[1:]))
    ttfts = sorted(t for t in ttfts if t is not None)

    def pct(xs, q):
        return (round(xs[min(len(xs) - 1, int(q * len(xs)))], 1)
                if xs else None)

    ds = router.fleet_stats()["disagg"]
    return {
        "role_split": role_split,
        "decode_streams": n_decode,
        "long_sessions": sessions,
        "decode_itl_ms": {"p50": pct(gaps, 0.50),
                          "p99": pct(gaps, 0.99),
                          "max": round(gaps[-1], 1) if gaps else None},
        "long_ttft_ms": {"p50": pct(ttfts, 0.50),
                         "max": round(ttfts[-1], 1) if ttfts else None},
        "error_frames": len(errors),
        "handoffs": ds["handoffs"],
        "fallbacks": ds["fallbacks"],
        "bytes_per_token": ds["bytes_per_token"],
        "tiers": ds["tiers"],
    }
    # Deliberately no engine shutdown (see _fleet_phase note); the
    # child prints its JSON and hard-exits.


def bench_fleet_disagg() -> dict:
    """The disaggregation acceptance pair (docs/ROUTER.md): the same
    mid-decode long-prompt burst against a role-split fleet (prefill
    tier hands KV to the decode tier over the migration wire) and a
    mixed control — role-split must protect decode inter-token p99,
    with long-prompt TTFT inside the priced-migration budget and zero
    client-visible error frames on both sides."""
    log("--- disagg 1/2: role-split (prefill|decode tiers) ---")
    split = _fleet_fabric_subprocess("BENCH_FLEET_DISAGG", "split")
    log("--- disagg 2/2: mixed control (same engines, no roles) ---")
    mixed = _fleet_fabric_subprocess("BENCH_FLEET_DISAGG", "mixed")
    gain = None
    if split["decode_itl_ms"]["p99"] and mixed["decode_itl_ms"]["p99"]:
        gain = round(mixed["decode_itl_ms"]["p99"]
                     / split["decode_itl_ms"]["p99"], 2)
    log(f"  decode ITL p99: split {split['decode_itl_ms']['p99']} ms "
        f"vs mixed {mixed['decode_itl_ms']['p99']} ms ({gain}x); "
        f"handoffs={split['handoffs']} "
        f"fallbacks={split['fallbacks']}; TTFT p50 split "
        f"{split['long_ttft_ms']['p50']} vs mixed "
        f"{mixed['long_ttft_ms']['p50']} ms; error frames "
        f"{split['error_frames']}+{mixed['error_frames']}")
    return {"split": split, "mixed": mixed,
            "decode_itl_p99_gain": gain,
            "error_frames": split["error_frames"]
            + mixed["error_frames"]}


def _fleet_fabric_subprocess(env_key: str, env_val: str) -> dict:
    """Run one fabric phase in a child process (fresh XLA state — the
    same isolation discipline as every other multi-engine bench)."""
    import subprocess

    env = _child_env(**{env_key: env_val})
    env["TPU_COMPILE_CACHE"] = "off"
    # Fabric children always dispatch through the fleet branch, even
    # when the parent is the standalone BENCH_MODE=disagg headline.
    env["BENCH_MODE"] = "fleet"
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, stdout=subprocess.PIPE, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"fleet fabric phase {env_key}={env_val} "
                           f"exited {proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_fleet_fabric(replicas: int, sessions: int) -> dict:
    """The fabric acceptance pair (docs/ROUTER.md): (1) drain-migrate
    vs drain-release follow-up TTFT on long sessions — migration must
    beat re-prefill; (2) a rolling restart of N replicas with zero
    client-visible error frames."""
    log("--- fabric 1/3: drain + follow-up, migration ON ---")
    mig = _fleet_fabric_subprocess("BENCH_FLEET_MIGRATE", "on")
    log("--- fabric 2/3: drain + follow-up, migration OFF "
        "(re-prefill) ---")
    pre = _fleet_fabric_subprocess("BENCH_FLEET_MIGRATE", "off")
    speedup = None
    if mig["followup_ttft_ms"]["p50"]:
        speedup = round(pre["followup_ttft_ms"]["p50"]
                        / mig["followup_ttft_ms"]["p50"], 2)
    log(f"  follow-up TTFT p50: migrate "
        f"{mig['followup_ttft_ms']['p50']} ms vs re-prefill "
        f"{pre['followup_ttft_ms']['p50']} ms ({speedup}x)")
    log(f"--- fabric 3/3: rolling restart of {replicas} replicas ---")
    roll = _fleet_fabric_subprocess("BENCH_FLEET_ROLLING",
                                    str(replicas))
    log(f"  rolling restart: {roll['error_frames']} error frames, "
        f"{roll['resumed_events']} resumed, "
        f"{roll['completed']}/{roll['streams']} streams completed")
    return {"migrate": mig, "reprefill": pre,
            "followup_ttft_speedup": speedup,
            "rolling_restart": roll}


# ---------------- overload mode (admission control) ----------------

async def bench_overload(cfg) -> dict:
    """Open-loop overload: arrivals above service capacity. Reports how
    the scheduler degrades — who was shed (immediately, with
    retry_after), who expired in the queue, and what queue wait the
    admitted requests actually paid."""
    from fasttalk_tpu.engine.engine import GenerationParams
    from fasttalk_tpu.engine.factory import build_engine
    from fasttalk_tpu.utils.errors import AdmissionRejected
    from fasttalk_tpu.utils.metrics import get_metrics

    arrival_s = float(os.environ.get("BENCH_ARRIVAL_MS", "25")) / 1000.0
    duration_s = float(os.environ.get("BENCH_OVERLOAD_S", "20"))
    deadline_s = float(os.environ.get("BENCH_DEADLINE_S", "2.0"))

    t0 = time.monotonic()
    engine = build_engine(cfg)
    log(f"engine built in {time.monotonic() - t0:.1f}s; warming up...")
    engine.warmup(cfg.warmup)
    engine.start()

    out = {"arrived": 0, "done": 0, "shed": 0, "expired": 0,
           "error": 0, "tokens": 0}
    max_depth = 0

    async def one(i: int) -> None:
        params = GenerationParams(temperature=0.7, top_k=40, top_p=0.9,
                                  max_tokens=MAX_TOKENS,
                                  deadline_s=deadline_s)
        sid = f"ov-s{i}"
        try:
            async for ev in engine.generate(
                    f"ov-{i}", sid,
                    [{"role": "user", "content": f"[{i}] {PROMPT}"}],
                    params):
                if ev["type"] == "done":
                    out["done"] += 1
                    out["tokens"] += ev["stats"]["tokens_generated"]
                elif ev["type"] == "error":
                    key = ("expired"
                           if ev.get("code") == "deadline_expired"
                           else "error")
                    out[key] += 1
        except AdmissionRejected as e:
            assert e.retry_after is not None  # shed always hints
            out["shed"] += 1
        finally:
            engine.release_session(sid)

    try:
        log("overload warmup (compile)...")
        await one(999_999)
        for k in out:
            out[k] = 0
        reset_slo_after_warmup()
        rate = 1.0 / arrival_s
        log(f"open loop: {rate:.0f} req/s for {duration_s:.0f}s, "
            f"deadline {deadline_s}s, queue bound "
            f"{cfg.sched_queue_bound}...")
        t1 = time.monotonic()
        tasks = []
        i = 0
        while time.monotonic() - t1 < duration_s:
            tasks.append(asyncio.create_task(one(i)))
            out["arrived"] += 1
            i += 1
            depth = engine.get_stats()["scheduler"]["depth"]
            max_depth = max(max_depth, depth)
            await asyncio.sleep(arrival_s)
        await asyncio.gather(*tasks)
        wall = time.monotonic() - t1
    finally:
        engine.shutdown()

    # SLO goodput (observability/slo.py): the fraction of completed
    # requests that met EVERY objective — the honest headline under
    # overload, where raw tok/s stays flat while admitted users wait.
    slo_goodput, slo_alert = slo_goodput_summary()
    qw = get_metrics().histogram("queue_wait_ms")
    arrived = max(1, out["arrived"])
    res = {
        "arrival_rate_rps": round(1.0 / arrival_s, 2),
        "duration_s": round(wall, 2),
        "queue_bound": cfg.sched_queue_bound,
        "max_queue_depth": max_depth,
        "arrived": out["arrived"],
        "admitted_done": out["done"],
        "shed": out["shed"],
        "expired": out["expired"],
        "errors": out["error"],
        "shed_rate": round(out["shed"] / arrived, 4),
        "expiry_rate": round(out["expired"] / arrived, 4),
        "goodput_tok_s": round(out["tokens"] / wall, 1),
        "slo_goodput": slo_goodput,
        "slo_alert": slo_alert,
        "queue_wait_ms": {"p50": round(qw.percentile(50), 1),
                          "p95": round(qw.percentile(95), 1),
                          "p99": round(qw.percentile(99), 1)},
    }
    log(f"  {res['arrived']} arrived: {res['admitted_done']} done, "
        f"{res['shed']} shed ({res['shed_rate']:.1%}), "
        f"{res['expired']} expired ({res['expiry_rate']:.1%}); "
        f"max depth {max_depth}/{cfg.sched_queue_bound}; "
        f"admitted queue-wait p50/p95/p99 "
        f"{res['queue_wait_ms']['p50']:.0f}/"
        f"{res['queue_wait_ms']['p95']:.0f}/"
        f"{res['queue_wait_ms']['p99']:.0f} ms; "
        f"goodput {res['goodput_tok_s']:.1f} tok/s; "
        f"SLO goodput {fmt_goodput(slo_goodput)} "
        f"(alert {res['slo_alert']})")
    if max_depth > cfg.sched_queue_bound:
        log(f"  WARNING: observed queue depth {max_depth} exceeded the "
            f"bound {cfg.sched_queue_bound}")
    return res


async def bench_structured(engine) -> dict:
    """BENCH_MODE=structured (docs/STRUCTURED.md): two questions.

    1. **Mask overhead** — constrained decode steps gather/unpack one
       packed bitmask row per slot per step inside the jitted sampler;
       target <5% tok/s cost. Measured with an unforced constraint
       (``[ab]{N}``: every step a choice, no jump-forward, no early
       accept) against an unconstrained ``ignore_eos`` control of the
       same length, single session, greedy.
    2. **Jump-forward savings** — a schema whose fixed punctuation and
       long property names compile to single-transition chains; same
       greedy document with jump-forward off vs on, reporting the
       forced-token fraction and the e2e delta.
    """
    from fasttalk_tpu.engine.engine import GenerationParams
    from fasttalk_tpu.utils.metrics import get_metrics

    n_tok = int(os.environ.get("BENCH_ST_TOKENS", "96"))
    greedy = dict(temperature=0.0, top_k=0, top_p=1.0)

    async def one(rid, params, prompt="measure"):
        t0 = time.monotonic()
        ttft = None
        text = ""
        stats = {}
        async for ev in engine.generate(rid, f"sess-{rid}",
                                        [{"role": "user",
                                          "content": prompt}], params):
            if ev["type"] == "token":
                if ttft is None:
                    ttft = (time.monotonic() - t0) * 1000.0
                text += ev["text"]
            elif ev["type"] == "done":
                stats = ev["stats"]
            elif ev["type"] == "error":
                raise RuntimeError(f"generation failed: {ev}")
        engine.release_session(f"sess-{rid}")
        return {"wall_s": time.monotonic() - t0, "ttft_ms": ttft or 0.0,
                "tokens": stats.get("tokens_generated", 0),
                "text": text}

    log("warmup (compiling plain + constrained decode shapes)...")
    await one("warm-plain", GenerationParams(max_tokens=8, **greedy))
    await one("warm-st", GenerationParams(
        max_tokens=8, **greedy,
        structured={"kind": "regex", "regex": "[ab]{512}"}))

    log("mask-overhead phase (unconstrained control vs [ab]{N})...")
    reps = int(os.environ.get("BENCH_ST_REPS", "3"))
    base_s, mask_s = [], []
    for i in range(reps):
        r = await one(f"base{i}", GenerationParams(
            max_tokens=n_tok, ignore_eos=True, **greedy))
        base_s.append(r["tokens"] / r["wall_s"])
        r = await one(f"mask{i}", GenerationParams(
            max_tokens=n_tok, **greedy,
            structured={"kind": "regex",
                        "regex": "[ab]{%d}" % (4 * n_tok)}))
        mask_s.append(r["tokens"] / r["wall_s"])
    base_tps = statistics.median(base_s)
    mask_tps = statistics.median(mask_s)
    overhead = 1.0 - mask_tps / base_tps

    log("jump-forward phase (forced-chain schema, off vs on)...")
    # Long single-transition runs: fixed punctuation + numeric
    # property names are forced for any tokenizer (digits are
    # single-byte tokens in every BPE's base alphabet).
    schema = {"type": "object", "properties": {
        "measurement_0123456789_a": {"enum": ["blue", "red"]},
        "measurement_0123456789_b": {"type": "boolean"},
        "measurement_0123456789_c": {"enum": [1, 2, 3]}}}
    sp = dict(structured={"kind": "json_schema", "schema": schema})
    jf_min, counter = engine._st_jf_min, get_metrics().counter(
        "structured_jump_forward_tokens_total")
    try:
        engine._st_jf_min = 0
        await one("jfw0", GenerationParams(max_tokens=256, **greedy,
                                           **sp))  # compile prefill
        offs = [await one(f"jf-off{i}", GenerationParams(
            max_tokens=256, **greedy, **sp)) for i in range(reps)]
        engine._st_jf_min = int(os.environ.get("BENCH_ST_JF_MIN", "4"))
        await one("jfw1", GenerationParams(max_tokens=256, **greedy,
                                           **sp))  # compile jump path
        before = counter.value
        ons = [await one(f"jf-on{i}", GenerationParams(
            max_tokens=256, **greedy, **sp)) for i in range(reps)]
        jumped = (counter.value - before) / reps
    finally:
        engine._st_jf_min = jf_min
    assert all(o["text"] == offs[0]["text"] for o in offs + ons), \
        "jump-forward changed the greedy document"
    off_ms = statistics.median(o["wall_s"] for o in offs) * 1000
    on_ms = statistics.median(o["wall_s"] for o in ons) * 1000
    doc_tokens = offs[0]["tokens"]
    res = {
        "unconstrained_tok_s": round(base_tps, 2),
        "constrained_tok_s": round(mask_tps, 2),
        "mask_overhead_frac": round(overhead, 4),
        "jump_forward": {
            "doc_tokens": doc_tokens,
            "forced_tokens": round(jumped, 1),
            "forced_fraction": round(jumped / max(1, doc_tokens), 3),
            "e2e_off_ms": round(off_ms, 1),
            "e2e_on_ms": round(on_ms, 1),
            "e2e_speedup": round(off_ms / on_ms, 2) if on_ms else None,
        },
        "fsm_compile_ms": get_metrics().histogram(
            "fsm_compile_ms").summary(),
    }
    log(f"  mask overhead {overhead:.1%} ({base_tps:.1f} -> "
        f"{mask_tps:.1f} tok/s); jump-forward forced "
        f"{res['jump_forward']['forced_fraction']:.0%} of "
        f"{doc_tokens} tokens, e2e {off_ms:.0f} -> {on_ms:.0f} ms "
        f"({res['jump_forward']['e2e_speedup']}x)")
    return res


# ---------------- chaos mode (docs/RESILIENCE.md) ----------------

async def _chaos_failover_drill(streams: int = 8,
                                delay_s: float = 0.004) -> dict:
    """Router failover recovery timing: N streams over two replicas,
    kill one mid-decode, measure kill->`resumed` latency per affected
    stream. FakeEngine-based on purpose — this measures the ROUTING
    layer's recovery deadline, not model throughput (the real-engine
    fleet is BENCH_MODE=fleet), so it runs in milliseconds and is
    device-independent."""
    from fasttalk_tpu.engine.engine import GenerationParams
    from fasttalk_tpu.engine.fake import FakeEngine
    from fasttalk_tpu.router import FleetRouter, ReplicaHandle
    from fasttalk_tpu.utils.errors import ErrorCategory, LLMServiceError

    class Mortal(FakeEngine):
        def __init__(self):
            super().__init__(reply="alpha beta gamma delta epsilon "
                             "zeta eta theta ", n_repeats=12,
                             delay_s=delay_s)
            self.dead = False

        def kill(self):
            self.dead = True
            self._started = False

        def check_connection(self):
            return not self.dead and self._started

        async def generate(self, rid, sid, messages, params):
            if self.dead:
                raise LLMServiceError(
                    "replica down", category=ErrorCategory.CONNECTION)
            async for ev in super().generate(rid, sid, messages,
                                             params):
                if self.dead:
                    raise LLMServiceError(
                        "replica died mid-stream",
                        category=ErrorCategory.CONNECTION)
                yield ev

    engines = [Mortal(), Mortal()]
    for e in engines:
        e.start()
    handles = [ReplicaHandle(f"r{i}", e, dead_probes=1)
               for i, e in enumerate(engines)]
    router = FleetRouter(handles, probe_interval_s=0,
                         failover_retries=2)
    router.start()
    kill_at: dict = {"t": None}
    resume_ms: list[float] = []
    errors = 0

    async def stream(i: int) -> None:
        nonlocal errors
        try:
            async for ev in router.generate(
                    f"chaos-req-{i}", f"chaos-sess-{i}",
                    [{"role": "user", "content": "hi"}],
                    GenerationParams(max_tokens=64, temperature=0.0,
                                     top_k=1)):
                if ev["type"] == "resumed" \
                        and kill_at["t"] is not None:
                    resume_ms.append(
                        (time.monotonic() - kill_at["t"]) * 1000)
                elif ev["type"] == "error":
                    errors += 1
        except Exception:
            errors += 1

    tasks = [asyncio.create_task(stream(i)) for i in range(streams)]
    await asyncio.sleep(delay_s * 8)  # streams underway on both
    kill_at["t"] = time.monotonic()
    engines[0].kill()
    await asyncio.gather(*tasks)
    affected = len({r["session_id"] for r in engines[0].requests_seen})
    router.shutdown()
    return {
        "streams": streams,
        "affected": affected,
        "resumed": len(resume_ms),
        "errors": errors,
        "resume_p50_ms": round(statistics.median(resume_ms), 2)
        if resume_ms else None,
    }


async def bench_chaos(engine) -> dict:
    """The failpoints-off CONTROL: does the fault-injection subsystem
    cost anything when FAULT_POINTS is unset? Interleaved phases —
    failpoints OFF vs ARMED-but-inert (a p=0 rule on the decode
    dispatch seam, so the registry lookup runs on every dispatch and
    never fires) — must agree within 1% tok/s. The MTTR and failover
    halves of BENCH_MODE=chaos live in _chaos_mttr_drill /
    _chaos_failover_drill (orchestrated by bench_chaos_main)."""
    from fasttalk_tpu.resilience import failpoints as fp

    log("warmup (compiling prefill + decode buckets)...")
    t0 = time.monotonic()
    await run_session(engine, 999, max_tokens=8)
    engine.release_session("bench-sess-999")
    await asyncio.gather(
        *(run_session(engine, 900 + i, max_tokens=8)
          for i in range(NUM_SESSIONS)))
    for i in range(NUM_SESSIONS):
        engine.release_session(f"bench-sess-{900 + i}")
    log(f"warmup done in {time.monotonic() - t0:.1f}s")
    reset_slo_after_warmup()

    async def tps_phase() -> float:
        # Several waves per phase: single-wave phases (~1 s on CPU
        # tiny) sit below the shared-box noise burst scale and swung
        # 2.5x between back-to-back identical runs; a phase must be
        # long enough to average over the bursts it cannot avoid.
        waves = int(os.environ.get("BENCH_CHAOS_WAVES", "3"))
        t0 = time.monotonic()
        tokens = 0
        for _ in range(waves):
            results = await asyncio.gather(
                *(run_session(engine, i, MAX_TOKENS)
                  for i in range(NUM_SESSIONS)))
            tokens += sum(r["tokens"] for r in results)
        wall = time.monotonic() - t0
        for i in range(NUM_SESSIONS):
            engine.release_session(f"bench-sess-{i}")
        return tokens / wall

    # (1) Control. Two noise sources dominate short CPU phases: the
    # client warms in over several runs (throughput climbs ~2x before
    # settling), and a shared box swings ±10-30% run to run. So:
    # warm until two consecutive phases agree within 5%, then measure
    # PAIRS — off and armed back to back, order alternating per pair
    # — and take the median of the pairwise armed/off ratios. Within
    # a pair (seconds apart) drift is small; the alternating order
    # cancels its direction; the median rejects outlier pairs. This
    # resolves a sub-1% effect where arm-wise medians or maxima of
    # the same phases swing several percent.
    log("control phases: failpoints off vs armed-inert (p=0)...")
    prev = await tps_phase()
    for _ in range(8):  # warm until stable
        cur = await tps_phase()
        if abs(cur - prev) / prev < 0.05:
            break
        prev = cur

    async def armed_phase() -> float:
        fp.activate("engine.decode.dispatch=error;p=0.0")
        try:
            return await tps_phase()
        finally:
            fp.clear()

    off_tps: list[float] = []
    armed_tps: list[float] = []
    ratios: list[float] = []
    for k in range(6):
        if k % 2 == 0:
            o = await tps_phase()
            a = await armed_phase()
        else:
            a = await armed_phase()
            o = await tps_phase()
        off_tps.append(o)
        armed_tps.append(a)
        ratios.append(a / o)
    tps_off = statistics.median(off_tps)
    tps_armed = statistics.median(armed_tps)
    delta = statistics.median(ratios) - 1.0
    log(f"  off {tps_off:.1f} tok/s vs armed-inert {tps_armed:.1f} "
        f"tok/s: delta {delta:+.2%} (target |delta| < 1%)")

    return {
        "control": {
            "off_tps": round(tps_off, 2),
            "armed_tps": round(tps_armed, 2),
            "delta_frac": round(delta, 4),
            "off_runs": [round(x, 2) for x in off_tps],
            "armed_runs": [round(x, 2) for x in armed_tps],
        },
    }


async def _chaos_mttr_drill(engine) -> dict:
    """One crash->restart MTTR drill (subprocess body): crash the
    engine thread under an injected crash_thread mid-decode,
    supervised-restart it, and time crash-detected -> restart-complete
    -> first post-restart token."""
    from fasttalk_tpu.resilience import failpoints as fp

    await run_session(engine, 999, max_tokens=8)  # compile warm
    engine.release_session("bench-sess-999")
    loop = asyncio.get_running_loop()
    victim = asyncio.create_task(run_session(engine, 700, 400))
    while not engine._running:
        await asyncio.sleep(0.005)
    fp.activate("engine.loop.tick=crash_thread;count=1")
    while engine.check_connection():
        await asyncio.sleep(0.005)
    fp.clear()
    t_dead = time.monotonic()
    ok = await loop.run_in_executor(None, engine.restart)
    assert ok, "supervised engine restart failed mid-bench"
    restart_ms = (time.monotonic() - t_dead) * 1000
    post = await run_session(engine, 800, max_tokens=8)
    try:
        await victim  # terminal internal_error from the crash
    except RuntimeError:
        pass
    return {"restart_ms": round(restart_ms, 1),
            "mttr_ms": round(restart_ms + post["ttft_ms"], 1)}


def _chaos_run_subprocess(phase: str) -> dict:
    """One chaos phase in its own interpreter (BENCH_CHAOS_PHASE=
    control|mttr). Subprocess isolation for the same reason as the
    multiturn/fleet phases: a worked engine's in-process teardown —
    and doubly a crash->restart cycle's abandoned dispatches — trips
    the pre-existing XLA-CPU client heap fragility that accelerator
    runtimes don't share. The parent therefore never builds an engine
    at all. One drill per process is also the honest MTTR shape:
    production restarts happen in a fresh process history, not after
    N prior crash cycles."""
    import subprocess

    env = _child_env(BENCH_CHAOS_PHASE=phase)
    last_err = ""
    for _attempt in range(2):  # native-runtime flakes get one retry
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env, capture_output=True, text=True, timeout=900)
        except subprocess.TimeoutExpired:
            # A wedged child is the hang-class flake; it gets the
            # same retry a crashed one does.
            last_err = "child timed out after 900s"
            continue
        for line in reversed(proc.stdout.strip().splitlines()):
            if line.startswith("{"):
                return json.loads(line)
        last_err = proc.stderr[-2000:]
    raise RuntimeError(
        f"chaos {phase} subprocess produced no JSON; stderr tail:\n"
        f"{last_err}")


def bench_chaos_main() -> dict:
    """BENCH_MODE=chaos orchestration: (1) the failpoints-off control,
    (2) three engine-restart MTTR drills — each subprocess-isolated —
    and (3) the router failover drill (FakeEngine fleet, in-proc)."""
    log("control phase (subprocess): failpoints off vs armed-inert...")
    control = _chaos_run_subprocess("control")
    log(f"  off {control['off_tps']} tok/s vs armed-inert "
        f"{control['armed_tps']} tok/s: delta "
        f"{control['delta_frac']:+.2%} (target |delta| < 1%)")

    log("engine-restart MTTR drills (subprocess-isolated)...")
    drills = []
    for k in range(3):
        d = _chaos_run_subprocess("mttr")
        drills.append(d)
        log(f"  drill {k + 1}: restart {d['restart_ms']:.0f} ms, "
            f"MTTR-to-first-token {d['mttr_ms']:.0f} ms")

    log("router failover drill (2 fake replicas, kill mid-decode)...")
    failover = asyncio.run(_chaos_failover_drill())
    log(f"  resumed {failover['resumed']}/{failover['affected']} "
        f"streams, {failover['errors']} errors, resume p50 "
        f"{failover['resume_p50_ms']} ms")

    return {
        "control": control,
        "restart_p50_ms": round(statistics.median(
            [d["restart_ms"] for d in drills]), 1),
        "mttr_p50_ms": round(statistics.median(
            [d["mttr_ms"] for d in drills]), 1),
        "mttr_runs_ms": [d["mttr_ms"] for d in drills],
        "failover": failover,
    }


# ---------------- profiler mode (sampler overhead control) -------------

async def bench_profiler(engine) -> dict:
    """The continuous-profiler zero-overhead control
    (docs/OBSERVABILITY.md "Continuous profiler and program
    attribution"): decode throughput with the host stack sampler OFF
    vs ON at PROF_HZ must agree within 1% — the contract that lets the
    sampler ship enabled in production. Same pairwise-interleaved
    design as the failpoints control (bench_chaos): warm until two
    consecutive phases agree, then take the median of back-to-back
    on/off ratios with alternating order (drift within a pair is
    small; alternation cancels its direction; the median rejects
    outlier pairs). The ON phases feed the host-gap cause
    decomposition, so the result also carries host_gap_causes and the
    per-program attribution next to the delta."""
    from fasttalk_tpu.observability import profiler as profmod
    from fasttalk_tpu.observability.perf import get_perf

    log("warmup (compiling prefill + decode buckets)...")
    t0 = time.monotonic()
    await run_session(engine, 999, max_tokens=8)
    engine.release_session("bench-sess-999")
    await asyncio.gather(
        *(run_session(engine, 900 + i, max_tokens=8)
          for i in range(NUM_SESSIONS)))
    for i in range(NUM_SESSIONS):
        engine.release_session(f"bench-sess-{900 + i}")
    log(f"warmup done in {time.monotonic() - t0:.1f}s")
    reset_slo_after_warmup()

    # This mode exists to measure the sampler, so it runs enabled
    # regardless of the ambient PROF_ENABLED — through the singleton,
    # so the perf ledger's host_gap_causes block sees its samples.
    os.environ["PROF_ENABLED"] = "true"
    profmod.reset_profiler()
    prof = profmod.get_profiler()

    async def tps_phase() -> float:
        # Several waves per phase (see bench_chaos.tps_phase for why).
        waves = int(os.environ.get("BENCH_PROF_WAVES", "3"))
        t0 = time.monotonic()
        tokens = 0
        for _ in range(waves):
            results = await asyncio.gather(
                *(run_session(engine, i, MAX_TOKENS)
                  for i in range(NUM_SESSIONS)))
            tokens += sum(r["tokens"] for r in results)
        wall = time.monotonic() - t0
        for i in range(NUM_SESSIONS):
            engine.release_session(f"bench-sess-{i}")
        return tokens / wall

    async def on_phase() -> float:
        prof.start()
        try:
            return await tps_phase()
        finally:
            prof.stop()

    log(f"control phases: sampler off vs on ({prof.hz:g} Hz)...")
    prev = await tps_phase()
    for _ in range(8):  # warm until stable
        cur = await tps_phase()
        if abs(cur - prev) / prev < 0.05:
            break
        prev = cur

    off_tps: list[float] = []
    on_tps: list[float] = []
    ratios: list[float] = []
    for k in range(6):
        if k % 2 == 0:
            o = await tps_phase()
            a = await on_phase()
        else:
            a = await on_phase()
            o = await tps_phase()
        off_tps.append(o)
        on_tps.append(a)
        ratios.append(a / o)
    tps_off = statistics.median(off_tps)
    tps_on = statistics.median(on_tps)
    delta = statistics.median(ratios) - 1.0
    log(f"  off {tps_off:.1f} tok/s vs sampling {tps_on:.1f} tok/s: "
        f"delta {delta:+.2%} (target |delta| < 1%)")

    rep = prof.report(top=5)
    perf = get_perf().summary()
    return {
        "control": {
            "off_tps": round(tps_off, 2),
            "on_tps": round(tps_on, 2),
            "delta_frac": round(delta, 4),
            "off_runs": [round(x, 2) for x in off_tps],
            "on_runs": [round(x, 2) for x in on_tps],
        },
        "sampler": {"hz": prof.hz, "samples": rep["samples"],
                    "errors": rep["errors"],
                    "dropped_stacks": rep["dropped_stacks"]},
        "host_gap_causes": perf.get("host_gap_causes"),
        "programs_top": perf.get("programs_top"),
    }


async def bench_engine(engine) -> dict:
    log("warmup (compiling prefill + decode buckets)...")
    t0 = time.monotonic()
    await run_session(engine, 999, max_tokens=8)
    engine.release_session("bench-sess-999")
    await asyncio.gather(
        *(run_session(engine, 900 + i, max_tokens=8)
          for i in range(NUM_SESSIONS)))
    for i in range(NUM_SESSIONS):
        engine.release_session(f"bench-sess-{900 + i}")
    log(f"warmup done in {time.monotonic() - t0:.1f}s")
    reset_slo_after_warmup()

    log("single-session run...")
    single = await run_session(engine, 0, MAX_TOKENS)
    engine.release_session("bench-sess-0")
    single_tps = single["tokens"] / single["wall_s"]
    log(f"  1 session: {single['tokens']} tok in {single['wall_s']:.2f}s "
        f"= {single_tps:.1f} tok/s, TTFT {single['ttft_ms']:.0f}ms")

    log(f"{NUM_SESSIONS} concurrent sessions...")
    t0 = time.monotonic()
    results = await asyncio.gather(
        *(run_session(engine, i, MAX_TOKENS) for i in range(NUM_SESSIONS)))
    wall = time.monotonic() - t0
    for i in range(NUM_SESSIONS):
        engine.release_session(f"bench-sess-{i}")
    total_tokens = sum(r["tokens"] for r in results)
    agg_tps = total_tokens / wall
    p50_ttft = statistics.median(r["ttft_ms"] for r in results)
    log(f"  {NUM_SESSIONS} sessions: {total_tokens} tok in {wall:.2f}s "
        f"= {agg_tps:.1f} tok/s aggregate, p50 TTFT {p50_ttft:.0f}ms")

    return {"single_tps": single_tps, "single_ttft_ms": single["ttft_ms"],
            "agg_tps": agg_tps, "p50_ttft_ms": p50_ttft}


def _device() -> dict:
    """The device this LEAF process computes on, as JAX reports it.
    Orchestrating parents never call this (nor anything else that
    imports jax or builds a Config) until their children have exited:
    a chip belongs to one process, and a parent that has touched JAX
    holds it while every child that needs it fails or hangs."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _print_phase(phase: dict) -> None:
    """A child phase's one JSON line, naming the device it ran on."""
    print(json.dumps({**phase, "device": _device()}), flush=True)


def _base_cfg(**extra):
    """The default bench configuration (leaf processes only)."""
    from fasttalk_tpu.utils.config import Config

    return Config(llm_provider="tpu", model_name=MODEL,
                  decode_slots=NUM_SESSIONS, max_model_len=2048,
                  default_context_window=2048, prefill_chunk=512,
                  dtype="bfloat16", port=PORT, monitoring_port=PORT + 1,
                  **extra,
                  # Plain chat serving path (no tool-section system
                  # prompt): keeps the measured prompt identical to the
                  # reference's bench conditions; the agent path has
                  # its own tests.
                  enable_agent=False,
                  # int8 weights are the serving default for the bench:
                  # the dequant-fused kernels stream int8 bytes
                  # (ops/pallas_int8.py), and it is the config the
                  # README's model table quotes.
                  quantize=os.environ.get("BENCH_QUANTIZE", "int8"))


def main() -> None:
    # Modes that orchestrate child processes import Config only inside
    # their child branches (see _device): everything above the leaf
    # phases is stdlib.
    if MODE == "multiturn":
        mt_sessions = int(os.environ.get("BENCH_MT_SESSIONS",
                                         str(NUM_SESSIONS)))
        # Slot pressure is the whole scenario: fewer slots than
        # sessions, so a follow-up turn always returns to an evicted
        # session.
        slots = int(os.environ.get("BENCH_MT_SLOTS",
                                   str(max(1, mt_sessions // 2))))
        if os.environ.get("BENCH_MT_PHASE"):
            # Child process: one phase with the budget the parent set.
            from fasttalk_tpu.utils.config import Config

            budget = float(os.environ.get("BENCH_KV_BUDGET_MB", "0"))
            cfg = Config(llm_provider="tpu", model_name=MODEL,
                         decode_slots=slots, max_model_len=2048,
                         default_context_window=2048,
                         prefill_chunk=512, dtype="bfloat16",
                         port=PORT, monitoring_port=PORT + 1,
                         enable_agent=False,
                         kv_host_budget_mb=budget,
                         quantize=os.environ.get("BENCH_QUANTIZE",
                                                 "int8"))
            turns = int(os.environ.get("BENCH_MT_TURNS", "3"))
            max_tokens = int(os.environ.get("BENCH_MT_MAX_TOKENS",
                                            "32"))
            phase = asyncio.run(
                _mt_phase(cfg, mt_sessions, turns, max_tokens))
            _print_phase(phase)
            return

        r = bench_multiturn()
        on_p50 = (r["on"]["followup_ttft_ms"] or {}).get("p50")
        print(json.dumps({
            "metric": (f"multiturn follow-up-turn TTFT p50 ms, {MODEL}: "
                       f"{r['sessions']} sessions x {r['turns']} turns "
                       f"on {slots} slots, host pool "
                       f"{r['kv_budget_mb']:.0f} MB (off p50 "
                       f"{r['off']['followup_ttft_ms']['p50']} ms, "
                       f"restore hit ratio "
                       f"{r['on']['restore_hit_ratio']}, p50 speedup "
                       f"{r['followup_ttft_p50_speedup']}x)"),
            "value": on_p50,
            "unit": "ms",
            # For this mode the baseline is the engine's own
            # re-prefill path: >1 means the restore tier is winning.
            "vs_baseline": r["followup_ttft_p50_speedup"],
            "multiturn": r,
        }), flush=True)
        return
    if MODE == "longctx":
        ctx = int(os.environ.get("BENCH_LC_CTX", "384"))
        sessions = int(os.environ.get("BENCH_LC_SESSIONS", "8"))
        slots = int(os.environ.get("BENCH_LC_SLOTS", "2"))
        max_tokens = int(os.environ.get("BENCH_LC_MAX_TOKENS", "16"))
        if os.environ.get("BENCH_LC_PHASE"):
            # Child process: one phase with the kv_quant the parent
            # set. Weight quantization stays OFF in both phases — it
            # is orthogonal to the KV tier and would only blur the
            # comparison; speculative decoding is off because the
            # int8 phase rejects it (compat matrix) and the control
            # must match.
            from fasttalk_tpu.models.configs import get_model_config
            from fasttalk_tpu.utils.config import Config

            m = get_model_config(MODEL)
            # The parked bucket every session lands in (kvcache/
            # offload.py kv_bucket): prompt + generation rounded up to
            # a power of two. The default budget holds ~3.5 bf16
            # entries → ~7 int8+scales entries: the capacity headline
            # is the measured ratio, not this sizing.
            bucket = 1 << (ctx + 96 - 1).bit_length()
            bf16_entry_mb = 2 * m.num_layers * bucket \
                * m.num_kv_heads * m.head_dim * 2 / 2**20
            budget = float(os.environ.get(
                "BENCH_LC_BUDGET_MB",
                str(round(3.5 * bf16_entry_mb, 3))))
            cfg = Config(llm_provider="tpu", model_name=MODEL,
                         decode_slots=slots, max_model_len=2048,
                         default_context_window=2048,
                         prefill_chunk=512, dtype="bfloat16",
                         port=PORT, monitoring_port=PORT + 1,
                         enable_agent=False, spec_decode="off",
                         quantize="none",
                         kv_host_budget_mb=budget,
                         kv_park_idle_s=0.0,
                         kv_quant=os.environ["BENCH_LC_PHASE"])
            phase = asyncio.run(
                _lc_phase(cfg, sessions, ctx, max_tokens))
            _print_phase(phase)
            return
        r = bench_longctx()
        print(json.dumps({
            "metric": (f"longctx parked-session capacity ratio "
                       f"(int8 KV vs bf16), {MODEL}: {r['sessions']} "
                       f"sessions x ~{r['ctx_tokens']} ctx tokens on "
                       f"a fixed {r['budget_mb']:.1f} MB host budget "
                       f"(bf16 {r['bf16']['parked_sessions']} x "
                       f"{r['bf16']['per_session_mb']} MB vs int8 "
                       f"{r['int8']['parked_sessions']} x "
                       f"{r['int8']['per_session_mb']} MB; restore "
                       f"p50 {r['bf16']['restore_p50_ms']} -> "
                       f"{r['int8']['restore_p50_ms']} ms, "
                       f"{r['restore_p50_speedup']}x; decode tok/s "
                       f"ratio {r['decode_tok_s_ratio']})"),
            "value": r["parked_capacity_ratio"],
            "unit": "x",
            # For this mode the baseline is the bf16 KV cache on the
            # same budget: >= 1.8 means the quantized tier is holding
            # ~double the sessions per byte.
            "vs_baseline": r["parked_capacity_ratio"],
            "longctx": r,
        }), flush=True)
        return
    if MODE == "int4":
        max_tokens = int(os.environ.get("BENCH_I4_MAX_TOKENS", "64"))
        slots = int(os.environ.get("BENCH_I4_SLOTS", "4"))
        if os.environ.get("BENCH_I4_PHASE"):
            # Child process: one weight tier. KV knobs at defaults and
            # spec decode off in every phase — only the weight tier
            # may differ between the children.
            from fasttalk_tpu.utils.config import Config

            cfg = Config(llm_provider="tpu", model_name=MODEL,
                         decode_slots=slots, max_model_len=512,
                         default_context_window=512,
                         prefill_chunk=512, dtype="bfloat16",
                         port=PORT, monitoring_port=PORT + 1,
                         enable_agent=False, spec_decode="off",
                         weight_quant=os.environ["BENCH_I4_PHASE"])
            phase = asyncio.run(_i4_phase(cfg, max_tokens))
            _print_phase(phase)
            return
        r = bench_int4()
        print(json.dumps({
            "metric": (f"int4 resident sessions x context envelope "
                       f"ratio (int4+scales weights vs bf16), {MODEL}: "
                       f"fixed {r['budget_mb']:.1f} MB HBM budget, "
                       f"weight bytes "
                       f"{r['weight_bytes']['off'] / 2**20:.1f} -> "
                       f"{r['weight_bytes']['int4'] / 2**20:.1f} MB "
                       f"(group {r['group']}), KV envelope "
                       f"{r['envelope_token_rows']['off']} -> "
                       f"{r['envelope_token_rows']['int4']} token-rows"
                       f"; decode tok/s off/int8/int4 "
                       f"{r['off']['decode_tok_s']}/"
                       f"{r['int8']['decode_tok_s']}/"
                       f"{r['int4']['decode_tok_s']} (int4 vs int8 "
                       f"{r['decode_tok_s_int4_vs_int8']})"),
            "value": r["envelope_ratio_int4_vs_bf16"],
            "unit": "x",
            # For this mode the baseline is bf16 weights on the SAME
            # budget: >= 2 means the 4-bit tier at least doubles what
            # the budget can hold resident.
            "vs_baseline": r["envelope_ratio_int4_vs_bf16"],
            "int4": r,
        }), flush=True)
        return
    if MODE == "roofline":
        slots = int(os.environ.get("BENCH_RF_SLOTS", "8"))
        max_tokens = int(os.environ.get("BENCH_RF_MAX_TOKENS", "24"))
        if os.environ.get("BENCH_RF_PHASE"):
            # Child process: one sweep cell. Weight quant off by
            # default so the KV-tier and kernel axes are the only
            # variables (the TPU driver can re-pin BENCH_QUANTIZE);
            # spec off because the int8 cells reject it and every cell
            # must measure the same decode family.
            from fasttalk_tpu.utils.config import Config

            kv_quant = os.environ.get("BENCH_RF_KV", "none")
            layout = os.environ.get("BENCH_RF_LAYOUT", "dense")
            kernel = os.environ.get("BENCH_RF_KERNEL", "xla")
            cfg = Config(llm_provider="tpu", model_name=MODEL,
                         decode_slots=slots, max_model_len=1024,
                         default_context_window=1024,
                         prefill_chunk=512, dtype="bfloat16",
                         port=PORT, monitoring_port=PORT + 1,
                         enable_agent=False, spec_decode="off",
                         quantize=os.environ.get("BENCH_QUANTIZE",
                                                 "none"),
                         kv_quant=kv_quant, kv_layout=layout,
                         kv_block_size=int(os.environ.get(
                             "KV_BLOCK_SIZE", "16")),
                         kv_host_budget_mb=0.0,
                         use_pallas_attention=(kernel == "pallas"))
            phase = asyncio.run(_rf_phase(cfg, max_tokens))
            _print_phase(phase)
            return
        r = bench_roofline()
        b = r["best"]
        frac = r["best_frac_of_ceiling"]
        print(json.dumps({
            "metric": (f"roofline sweep best decode tok/s, {MODEL}: "
                       f"{len(r['rows'])} cells (kv x layout x kernel "
                       f"x steps_per_call) at {slots} slots; best = "
                       f"kv={b['kv_quant']} {b['kv_layout']} "
                       f"{b['kernel']} steps={b['steps_per_call']}"
                       + (f", {frac:.0%} of first-order HBM ceiling"
                          if frac is not None else
                          " (no HBM peak for this device kind)")),
            "value": b["tok_s"],
            "unit": "tok/s",
            "vs_baseline": round(b["tok_s"] / BASELINE_TOKS, 2),
            "roofline": r,
        }), flush=True)
        return
    if MODE == "radix":
        agents = int(os.environ.get("BENCH_RX_AGENTS", "4"))
        turns = int(os.environ.get("BENCH_RX_TURNS", "4"))
        max_tokens = int(os.environ.get("BENCH_RX_MAX_TOKENS", "32"))
        if os.environ.get("BENCH_RX_PHASE"):
            # Child process: one phase. Paged layout in BOTH phases
            # (the tree requires it, and the off control must differ
            # by exactly one knob); host pool off so park/restore
            # can't serve the prefix either way.
            from fasttalk_tpu.utils.config import Config

            on = os.environ["BENCH_RX_PHASE"] == "on"
            cfg = Config(llm_provider="tpu", model_name=MODEL,
                         decode_slots=agents, max_model_len=2048,
                         default_context_window=2048,
                         prefill_chunk=512, dtype="bfloat16",
                         port=PORT, monitoring_port=PORT + 1,
                         enable_agent=False, spec_decode="off",
                         kv_host_budget_mb=0.0, kv_layout="paged",
                         kv_radix_enabled=on,
                         quantize=os.environ.get("BENCH_QUANTIZE",
                                                 "int8"))
            out = asyncio.run(_rx_phase(cfg, agents, turns,
                                        max_tokens))
            _print_phase(out)
            return
        r = bench_radix()
        on_p50 = (r["on"]["followup_ttft_ms"] or {}).get("p50")
        print(json.dumps({
            "metric": (f"radix follow-up-turn TTFT p50 ms, {MODEL}: "
                       f"{r['agents']} agents x {r['turns']} turns, "
                       f"fresh session per turn (off p50 "
                       f"{r['off']['followup_ttft_ms']['p50']} ms, "
                       f"hit rate {r['hit_rate']}, bytes saved "
                       f"{r['bytes_saved']}, p50 speedup "
                       f"{r['followup_ttft_p50_speedup']}x)"),
            "value": on_p50,
            "unit": "ms",
            # Baseline is the engine's own full-re-prefill path:
            # >1 means the tree is winning; acceptance wants >= 2.
            "vs_baseline": r["followup_ttft_p50_speedup"],
            "radix": r,
        }), flush=True)
        return
    if MODE == "paged":
        sessions = int(os.environ.get("BENCH_PG_SESSIONS", "8"))
        max_len = int(os.environ.get("BENCH_PG_MAX_LEN", "2048"))
        rows = int(os.environ.get("BENCH_PG_KV_ROWS", "6144"))
        bs = int(os.environ.get("KV_BLOCK_SIZE", "16"))
        max_tokens = int(os.environ.get("BENCH_PG_MAX_TOKENS", "16"))
        if os.environ.get("BENCH_PG_PHASE"):
            # Child process: one (phase, layout) pair. Weight quant
            # and spec decode off in every phase — orthogonal knobs
            # would only blur the layout comparison; the host pool is
            # off so admission capacity is purely the device layout's.
            from fasttalk_tpu.utils.config import Config

            phase = os.environ["BENCH_PG_PHASE"]
            layout = os.environ["BENCH_PG_LAYOUT"]
            common = dict(llm_provider="tpu", model_name=MODEL,
                          prefill_chunk=512, dtype="bfloat16",
                          port=PORT, monitoring_port=PORT + 1,
                          enable_agent=False, spec_decode="off",
                          quantize="none", kv_host_budget_mb=0.0,
                          kv_layout=layout, kv_block_size=bs)
            if phase == "admission":
                slots = (sessions if layout == "paged"
                         else max(1, rows // max_len))
                cfg = Config(decode_slots=slots, max_model_len=max_len,
                             default_context_window=max_len,
                             kv_pool_blocks=(rows // bs
                                             if layout == "paged"
                                             else 0),
                             **common)
                out = asyncio.run(_pg_admission_phase(
                    cfg, sessions, _pg_mixed_contexts(sessions,
                                                      max_len),
                    max_tokens))
            else:
                # Throughput pair: identical slot count, paged pool
                # at the dense-equivalent size — the overhead control.
                tslots = int(os.environ.get("BENCH_PG_TPUT_SLOTS",
                                            "4"))
                cfg = Config(decode_slots=tslots, max_model_len=512,
                             default_context_window=512, **common)
                out = asyncio.run(_pg_tput_phase(cfg, 64))
            _print_phase(out)
            return
        r = bench_paged()
        print(json.dumps({
            "metric": (f"paged-KV peak concurrent sessions on a fixed "
                       f"{r['kv_rows_budget']}-row KV budget, {MODEL}: "
                       f"mixed contexts {r['contexts']}, dense "
                       f"{r['admission']['dense']['peak_concurrent']} "
                       f"(hard cap {r['dense_slots']} slots) vs paged "
                       f"{r['admission']['paged']['peak_concurrent']} "
                       f"({r['concurrent_ratio']}x); short-context "
                       f"decode tok/s ratio "
                       f"{r['throughput']['ratio']}; aliased-prefix "
                       f"savings {r['alias_saved_rows']} rows"),
            "value": r["admission"]["paged"]["peak_concurrent"],
            "unit": "sessions",
            # For this mode the baseline is the dense layout on the
            # SAME budget: > 1 means block-granular admission is
            # holding more of the mixed fleet resident.
            "vs_baseline": r["concurrent_ratio"],
            "paged": r,
        }), flush=True)
        return
    if MODE == "disagg":
        # The role-split-vs-mixed pair standalone (the same phases the
        # fleet headline tail carries), with the decode ITL p99 gain
        # as the gated value.
        d = bench_fleet_disagg()
        print(json.dumps({
            "metric": (f"disagg decode ITL p99 gain, {MODEL}: "
                       f"role-split (prefill|decode tiers) vs mixed "
                       f"on 2 replicas (split p99 "
                       f"{d['split']['decode_itl_ms']['p99']} ms vs "
                       f"mixed {d['mixed']['decode_itl_ms']['p99']} "
                       f"ms; {d['split']['handoffs']} handoffs, "
                       f"{d['split']['fallbacks']} fallbacks; long "
                       f"TTFT p50 {d['split']['long_ttft_ms']['p50']} "
                       f"vs {d['mixed']['long_ttft_ms']['p50']} ms; "
                       f"{d['error_frames']} error frames)"),
            "value": d["decode_itl_p99_gain"],
            "unit": "x",
            # >1 means the split protected the decode tail.
            "vs_baseline": d["decode_itl_p99_gain"],
            "disagg": d,
        }), flush=True)
        return
    if MODE == "fleet":
        replicas = int(os.environ.get("BENCH_FLEET_REPLICAS", "2"))
        sessions = int(os.environ.get("BENCH_FLEET_SESSIONS", "8"))
        slots = int(os.environ.get("BENCH_FLEET_SLOTS",
                                   str(max(1, sessions // replicas))))
        max_tokens = int(os.environ.get("BENCH_FLEET_MAX_TOKENS", "32"))
        if os.environ.get("BENCH_FLEET_MIGRATE"):
            # Child: one side of the migration-vs-reprefill pair.
            on = os.environ["BENCH_FLEET_MIGRATE"] == "on"
            phase = asyncio.run(_fleet_migration_phase(
                _fleet_fabric_cfg(slots), on,
                int(os.environ.get("BENCH_FLEET_MIG_SESSIONS", "4"))))
            _print_phase(phase)
            sys.stdout.flush()
            os._exit(0)
        if os.environ.get("BENCH_FLEET_DISAGG"):
            # Child: one side of the role-split-vs-mixed pair.
            split = os.environ["BENCH_FLEET_DISAGG"] == "split"
            phase = asyncio.run(_fleet_disagg_phase(
                _fleet_fabric_cfg(slots), split,
                int(os.environ.get("BENCH_FLEET_DISAGG_SESSIONS",
                                   "2"))))
            _print_phase(phase)
            sys.stdout.flush()
            os._exit(0)
        if os.environ.get("BENCH_FLEET_ROLLING"):
            # Child: the rolling-restart drill.
            n = int(os.environ["BENCH_FLEET_ROLLING"])
            phase = asyncio.run(_fleet_rolling_phase(
                _fleet_fabric_cfg(slots), n, sessions))
            _print_phase(phase)
            sys.stdout.flush()
            os._exit(0)
        if os.environ.get("BENCH_FLEET_PHASE"):
            # Child process: one fleet size, then hard-exit (no XLA
            # multi-engine teardown).
            from fasttalk_tpu.utils.config import Config

            n = int(os.environ["BENCH_FLEET_PHASE"])
            cfg = Config(llm_provider="tpu", model_name=MODEL,
                         decode_slots=slots, max_model_len=2048,
                         default_context_window=2048,
                         prefill_chunk=512, dtype="bfloat16",
                         port=PORT, monitoring_port=PORT + 1,
                         enable_agent=False,
                         quantize=os.environ.get("BENCH_QUANTIZE",
                                                 "int8"))
            phase = asyncio.run(_fleet_phase(cfg, n, sessions,
                                             max_tokens))
            _print_phase(phase)
            sys.stdout.flush()
            os._exit(0)
        r = bench_fleet(replicas, sessions, slots)
        fabric = bench_fleet_fabric(replicas, sessions)
        r["fabric"] = fabric
        disagg = bench_fleet_disagg()
        r["disagg"] = disagg
        fo = (r["fleet"].get("failover") or {})
        roll = fabric["rolling_restart"]
        log(f"fabric headline: migration follow-up TTFT "
            f"{fabric['followup_ttft_speedup']}x vs re-prefill; "
            f"rolling restart {roll['error_frames']} error frames / "
            f"{roll['resumed_events']} resumed")
        log(f"disagg headline: decode ITL p99 gain "
            f"{disagg['decode_itl_p99_gain']}x (role-split vs mixed), "
            f"{disagg['split']['handoffs']} handoffs, "
            f"{disagg['error_frames']} error frames")
        print(json.dumps({
            "metric": (f"fleet aggregate WS tok/s, {MODEL}: "
                       f"{r['sessions']} sessions on "
                       f"{r['fleet']['replicas']} replicas x "
                       f"{r['slots_per_replica']} slots, "
                       f"{r['cores']} core(s) (single-replica"
                       f" {r['single']['agg_tps']} tok/s, speedup "
                       f"{r['agg_tps_speedup']}x, p50 TTFT speedup "
                       f"{r['p50_ttft_speedup']}x; failover resumed "
                       f"{fo.get('resumed')}/{fo.get('affected')} "
                       f"streams, {fo.get('errors')} errors, resume "
                       f"p50 "
                       f"{(fo.get('resume_latency_ms') or {}).get('p50')}"
                       f" ms; drain-migrate follow-up TTFT "
                       f"{fabric['followup_ttft_speedup']}x vs "
                       f"re-prefill, rolling restart "
                       f"{roll['error_frames']} error frames / "
                       f"{roll['resumed_events']} resumed; disagg "
                       f"decode ITL p99 gain "
                       f"{disagg['decode_itl_p99_gain']}x role-split "
                       f"vs mixed, {disagg['split']['handoffs']} "
                       f"handoffs, {disagg['error_frames']} error "
                       f"frames)"),
            "value": r["fleet"]["agg_tps"],
            "unit": "tok/s",
            # For this mode the baseline is the single-replica run:
            # >1 means scaling out is buying capacity.
            "vs_baseline": r["agg_tps_speedup"],
            "fleet": r,
            "disagg": disagg,
        }), flush=True)
        return
    if MODE == "overload":
        # Small bound + short deadline so the open-loop scenario
        # actually exercises shed AND expiry within the run.
        r = asyncio.run(bench_overload(_base_cfg(
            sched_queue_bound=int(os.environ.get("BENCH_QUEUE_BOUND",
                                                 "32")),
            sched_default_deadline_s=float(
                os.environ.get("BENCH_DEADLINE_S", "2.0")))))
        r["perf"] = perf_attribution()
        print(json.dumps({
            "metric": (f"overload goodput tok/s, {MODEL}: open-loop "
                       f"{r['arrival_rate_rps']:.0f} req/s x "
                       f"{r['duration_s']:.0f}s, bound "
                       f"{r['queue_bound']} (max depth "
                       f"{r['max_queue_depth']}), shed "
                       f"{r['shed_rate']:.1%}, expired "
                       f"{r['expiry_rate']:.1%}, admitted queue-wait "
                       f"p50/p95/p99 {r['queue_wait_ms']['p50']:.0f}/"
                       f"{r['queue_wait_ms']['p95']:.0f}/"
                       f"{r['queue_wait_ms']['p99']:.0f} ms, SLO "
                       f"goodput {fmt_goodput(r['slo_goodput'])}"),
            "value": r["goodput_tok_s"],
            "unit": "tok/s",
            "vs_baseline": round(r["goodput_tok_s"] / BASELINE_TOKS, 2),
            "overload": r,
        }), flush=True)
        return
    if MODE == "structured":
        from fasttalk_tpu.engine.factory import build_engine

        t0 = time.monotonic()
        engine = build_engine(_base_cfg())
        engine.start()
        log(f"engine up in {time.monotonic() - t0:.1f}s")
        try:
            r = asyncio.run(bench_structured(engine))
        finally:
            engine.shutdown()
        jf = r["jump_forward"]
        print(json.dumps({
            "metric": (f"structured mask overhead frac, {MODEL}: "
                       f"constrained {r['constrained_tok_s']} vs "
                       f"unconstrained {r['unconstrained_tok_s']} "
                       f"tok/s greedy (target < 0.05); jump-forward "
                       f"forced {jf['forced_fraction']:.0%} of "
                       f"{jf['doc_tokens']} tokens, e2e "
                       f"{jf['e2e_off_ms']:.0f} -> "
                       f"{jf['e2e_on_ms']:.0f} ms "
                       f"({jf['e2e_speedup']}x)"),
            "value": r["mask_overhead_frac"],
            "unit": "frac",
            # For this mode the baseline is the unconstrained tok/s on
            # the same engine: the ratio shows what the mask costs.
            "vs_baseline": round(r["constrained_tok_s"]
                                 / r["unconstrained_tok_s"], 3),
            "structured": r,
        }), flush=True)
        return
    if MODE == "chaos":
        phase = os.environ.get("BENCH_CHAOS_PHASE", "")
        if phase in ("control", "mttr"):
            # Child process: one phase, then hard-exit (a worked
            # engine's in-process XLA-CPU teardown — let alone a
            # crash->restart cycle's abandoned dispatches — is the
            # documented heap-corruption trap the multiturn/fleet
            # benches also isolate away).
            from fasttalk_tpu.engine.factory import build_engine

            engine = build_engine(_base_cfg())
            engine.start()
            if phase == "control":
                d = asyncio.run(bench_chaos(engine))["control"]
            else:
                d = asyncio.run(_chaos_mttr_drill(engine))
            _print_phase(d)
            sys.stdout.flush()
            os._exit(0)
        r = bench_chaos_main()
        fo = r["failover"]
        ctl = r["control"]
        print(json.dumps({
            "metric": (f"chaos engine-restart MTTR-to-first-token p50 "
                       f"ms, {MODEL} (restart p50 "
                       f"{r['restart_p50_ms']} ms over 3 injected "
                       f"crash_thread drills); failpoints-off control "
                       f"delta {ctl['delta_frac']:+.2%} "
                       f"(off {ctl['off_tps']} vs armed-inert "
                       f"{ctl['armed_tps']} tok/s, target < 1%); "
                       f"router failover resumed {fo['resumed']}/"
                       f"{fo['affected']} streams, {fo['errors']} "
                       f"errors, resume p50 {fo['resume_p50_ms']} ms"),
            "value": r["mttr_p50_ms"],
            "unit": "ms",
            # For this mode the baseline is the failpoints-off phase:
            # ~1.0 IS the result (armed-inert costs nothing).
            "vs_baseline": round(ctl["armed_tps"] / ctl["off_tps"], 3),
            "chaos": r,
        }), flush=True)
        return
    if MODE == "profiler":
        from fasttalk_tpu.engine.factory import build_engine

        t0 = time.monotonic()
        engine = build_engine(_base_cfg())
        engine.start()
        log(f"engine up in {time.monotonic() - t0:.1f}s")
        try:
            r = asyncio.run(bench_profiler(engine))
        finally:
            engine.shutdown()
        ctl = r["control"]
        print(json.dumps({
            "metric": (f"continuous-profiler overhead delta frac, "
                       f"{MODEL}: sampler off {ctl['off_tps']} vs on "
                       f"{ctl['on_tps']} tok/s at "
                       f"{r['sampler']['hz']:g} Hz "
                       f"({r['sampler']['samples']} samples; target "
                       f"|delta| < 0.01)"),
            "value": ctl["delta_frac"],
            "unit": "frac",
            # For this mode the baseline is the sampler-off phase:
            # ~1.0 IS the result (sampling-on costs nothing).
            "vs_baseline": round(ctl["on_tps"] / ctl["off_tps"], 3),
            "profiler": r,
        }), flush=True)
        return
    if MODE == "ws":
        r = asyncio.run(bench_ws(_base_cfg()))
        seam = "WebSocket"
    else:
        from fasttalk_tpu.engine.factory import build_engine

        t0 = time.monotonic()
        engine = build_engine(_base_cfg())
        engine.start()
        log(f"engine up in {time.monotonic() - t0:.1f}s")
        try:
            r = asyncio.run(bench_engine(engine))
        finally:
            engine.shutdown()
        seam = "engine-seam"

    # SLO goodput over the measured passes (warmup requests cleared
    # after compiles landed): the fraction of requests that met every
    # latency objective, next to the raw throughput headline.
    slo_goodput, _ = slo_goodput_summary()
    slo_note = "" if slo_goodput is None \
        else f"; SLO goodput {fmt_goodput(slo_goodput)}"
    perf = perf_attribution()
    if perf is not None:
        log(f"  perf attribution: busy {perf['device_busy_frac']:.0%} "
            f"/ host gap {perf['host_gap_frac']:.0%} / idle "
            f"{perf['idle_frac']:.0%}; occupancy "
            f"{perf['occupancy_mean']}; padding waste "
            f"{perf['padding_waste_frac']}; MFU {perf['mfu']}")
    print(json.dumps({
        "metric": (f"{seam} output tok/s, {MODEL}, "
                   f"{NUM_SESSIONS} concurrent sessions (p50 TTFT "
                   f"{r['p50_ttft_ms']:.0f}ms; 1-session "
                   f"{r['single_tps']:.1f} tok/s{slo_note})"),
        "value": round(r["agg_tps"], 1),
        "unit": "tok/s",
        "vs_baseline": round(r["agg_tps"] / BASELINE_TOKS, 2),
        "device": _device(),
        **({} if slo_goodput is None
           else {"slo_goodput": slo_goodput}),
        **({} if perf is None else {"perf": perf}),
    }), flush=True)


if __name__ == "__main__":
    main()
