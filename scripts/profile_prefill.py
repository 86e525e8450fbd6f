"""Split the prefill->first-token device time: dispatch+fetch round
trip, prefill call wall time per (bucket, group), decode-call wall
time, fetch latency.

The TTFT profiler (scripts/profile_ttft.py) shows ~all of WS TTFT is
prefill_dispatch -> first_ready; this isolates what that chunk is made
of on the real device.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from fasttalk_tpu.engine.factory import build_engine
from fasttalk_tpu.observability.perf import PerfLedger, program_key
from fasttalk_tpu.observability.trace import Tracer
from fasttalk_tpu.utils.config import Config

REPS = 10

# Standalone step ledger: timed loops
# stamped with a program key land in a PerfLedger, so the script ends
# with the per-program attribution table GET /perf serves live.
_TRACER = Tracer(enabled=True)
_LEDGER = PerfLedger(tracer=_TRACER, window_s=3600.0)


def timed(label, fn, reps=REPS, program=None, **pattrs):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1000)
    if program is not None:
        prog = program_key(program, **pattrs)
        end = time.monotonic()
        dt = float(np.median(ts)) / 1e3
        for i in range(reps):
            t1 = end - (reps - 1 - i) * dt
            _TRACER.step("engine_op", t1 - dt, t1, kind=program,
                         program=prog)
    print(f"  {label:44s} p50 {float(np.median(ts)):8.2f} ms  "
          f"min {min(ts):8.2f}  max {max(ts):8.2f}")
    return float(np.median(ts))


def print_programs() -> None:
    progs = (_LEDGER.report().get("programs") or {})
    rows = progs.get("by_program") or []
    if not rows:
        return
    print("== per-program device time (observability/perf.py "
          "ledger) ==", flush=True)
    for e in rows:
        print(f"  {e['busy_s']:8.3f}s {e['frac_of_busy']:7.1%} "
              f"x{e['calls']:<4d} {e['program']}")
    print(f"  {progs['total_busy_s']:8.3f}s total device busy "
          f"(per-program seconds sum to this by construction)")


def main() -> None:
    print(f"devices: {jax.devices()}")
    one = jnp.ones((), jnp.float32)
    timed("tiny-op dispatch+fetch round trip",
          lambda: np.asarray(one + 1.0))

    cfg = Config(llm_provider="tpu", model_name="llama3.2:1b",
                 decode_slots=16, max_model_len=2048,
                 default_context_window=2048, prefill_chunk=512,
                 dtype="bfloat16", enable_agent=False, quantize="int8")
    engine = build_engine(cfg)
    engine.warmup("fast")

    S = engine.num_slots
    inactive = engine._put(np.zeros((S,), bool))

    def decode_call(steps):
        fn = engine._get_decode_fn(512, steps)
        (engine.cache, engine._counts_dev, toks, engine._cur_tokens,
         engine._positions_dev, engine._rng_dev) = fn(
            engine.params, engine.cache, engine._counts_dev,
            engine._cur_tokens, engine._positions_dev, inactive,
            engine._temps_dev, engine._topks_dev, engine._topps_dev,
            engine._reps_dev, engine._press_dev, engine._freqs_dev,
            engine._rng_dev)
        return toks

    def prefill_call(bucket, gp, fetch):
        ctx = 512
        fn = engine._get_batched_prefill_fn(bucket, gp, ctx)
        rowcfg = np.zeros((gp, 7), np.float32)
        rowcfg[:, 0] = np.arange(S, S + gp)
        rowcfg[:, 4:] = (1.0, 40, 0.9)
        (engine.cache, firsts, engine._cur_tokens, engine._rng_dev) = fn(
            engine.params, engine.cache,
            np.zeros((gp, bucket), np.int32), rowcfg,
            engine._cur_tokens, engine._rng_dev)
        if fetch:
            np.asarray(firsts)
        return firsts

    # Warm the exact shapes used below.
    for gp in (1, S):
        np.asarray(prefill_call(64, gp, False))
    jax.block_until_ready(decode_call(8))

    timed("prefill b=64 g=1, DISPATCH only",
          lambda: prefill_call(64, 1, False),
          program="batched_prefill_dispatch", chunk=64, group=1)
    for gp in (1, 2, 4, 8, S):
        np.asarray(prefill_call(64, gp, False))  # warm shape
        timed(f"prefill b=64 g={gp} + firsts fetch",
              lambda gp=gp: prefill_call(64, gp, True),
              program="batched_prefill", chunk=64, group=gp, ctx=512)

    def settled_fetch(gp):
        firsts = prefill_call(64, gp, False)
        time.sleep(0.5)  # compute certainly done; fetch cost only
        t0 = time.perf_counter()
        np.asarray(firsts)
        return (time.perf_counter() - t0) * 1000

    for gp in (1, S):
        vals = [settled_fetch(gp) for _ in range(6)]
        print(f"  settled fetch after g={gp:2d} prefill"
              f"{'':14s} p50 {float(np.median(vals)):8.2f} ms  "
              f"min {min(vals):.2f} max {max(vals):.2f}")
    timed("decode call 8 steps + token fetch",
          lambda: np.asarray(decode_call(8)),
          program="decode", kv_len=512, steps=8)
    timed("decode dispatch only",
          lambda: decode_call(8),
          program="decode_dispatch", kv_len=512, steps=8)
    # Pipelined decode: dispatch N, then fetch the first — models the
    # engine's steady state where fetch overlaps the next call.
    t0 = time.perf_counter()
    toks = [decode_call(8) for _ in range(10)]
    for t in toks:
        np.asarray(t)
    wall = (time.perf_counter() - t0) * 1000
    print(f"  {'10 pipelined decode calls (80 steps)':44s} "
          f"total {wall:8.2f} ms -> {wall / 80:.2f} ms/step")

    # Prefill with a decode call queued in front (the burst situation).
    def queued(gp):
        decode_call(8)
        firsts = prefill_call(64, gp, False)
        np.asarray(firsts)

    timed("decode(8) then prefill g=1 + fetch", lambda: queued(1))
    timed(f"decode(8) then prefill g={S} + fetch", lambda: queued(S))


if __name__ == "__main__":
    try:
        main()
    finally:
        print_programs()
