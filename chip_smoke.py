#!/usr/bin/env python3
"""Chip smoke: does the serving path start and answer on the accelerator?

Drives the system's main path once, through the entry points a user
calls, at the published widths of ``llama3.2:1b`` (16 layers, hidden
2048, 32/8 heads x 64, vocab 128256; random weights from the engine's
fixed seed, the bundled bench tokenizer):

1. a probe child asks JAX what it sees — no accelerator, no run;
2. ``python main.py websocket`` with the ``run-tpu.sh`` defaults
   (bf16, 16 slots, 8192 context, TPU_WARMUP=fast) on free ports; as a
   client: three sequential WebSocket turns of 64 tokens (the 2nd and
   3rd in one session: the resident-KV turn), a burst of 16 concurrent
   sessions x 32 tokens, one streaming POST /v1/chat/completions;
   then /models, /profiler/memory, /perf and /metrics.json must name a
   TPU with a memory limit and a roofline peak, zero engine restarts,
   and SIGTERM must exit 0;
3. the same server again: the compile cache must gain no entry during
   that start (cold and warm engine-up seconds are printed as set-up
   times, not as metrics);
4. ``scripts/check_kernels.py`` in a child of its own: every Pallas
   entry point compiled (never interpreted) and agreeing with XLA.

This process never imports jax: a chip belongs to one process, and
only one child that needs it is alive at a time. Exit 0 and a last
stdout line ``{"ok": true, "device": {...}}`` mean every phase passed;
anything else (no chip, a dead child, a failed assertion, a time limit)
is a non-zero exit with the reason on stderr and no result line.

``--dry-run-cpu`` walks the same phases on the CPU at ``test-tiny`` to
debug this script without a chip. It can never report a pass: its last
line says ``"ok": false`` and it exits 3.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150.0  # the driver allows 1200 s, compilation included
T0 = time.monotonic()

PROBE = (
    "import json, jax, jaxlib, importlib.metadata as md\n"
    "d = jax.devices()\n"
    "try: libtpu = md.version('libtpu')\n"
    "except md.PackageNotFoundError: libtpu = None\n"
    "print(json.dumps({'platform': d[0].platform,"
    " 'kind': d[0].device_kind, 'count': len(d),"
    " 'jax': jax.__version__, 'jaxlib': jaxlib.__version__,"
    " 'libtpu': libtpu}))\n")


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    say(f"ok: {what}")


def remaining() -> float:
    left = DEADLINE_S - (time.monotonic() - T0)
    if left <= 0:
        raise SmokeFailure(f"time limit: {DEADLINE_S:.0f}s used up")
    return left


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def log_tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return "<no log>"


def http_json(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode())


def cache_entries(cache_dir: str) -> set[str]:
    try:
        return set(os.listdir(cache_dir))
    except FileNotFoundError:
        return set()


# ---------------------------------------------------------------- children

def run_child(name: str, argv: list[str], env: dict, timeout: float
              ) -> subprocess.CompletedProcess:
    """Run a short child to completion; it is the only process on the
    chip while it lives."""
    say(f"child {name}: {' '.join(argv[1:])[:80]}")
    try:
        return subprocess.run(argv, env=env, cwd=HERE, text=True,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE,
                              timeout=min(timeout, remaining()))
    except subprocess.TimeoutExpired as e:
        raise SmokeFailure(f"child {name} exceeded {e.timeout:.0f}s") \
            from None


class Server:
    """One ``python main.py websocket`` child."""

    def __init__(self, name: str, env: dict, log_dir: str):
        self.name = name
        self.port, self.mon = free_port(), free_port()
        self.log_path = os.path.join(log_dir, f"chip_smoke_{name}.log")
        self._log = open(self.log_path, "w")
        env = dict(env, LLM_HOST="127.0.0.1", LLM_PORT=str(self.port),
                   LLM_MONITORING_HOST="127.0.0.1",
                   LLM_MONITORING_PORT=str(self.mon))
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "main.py"), "websocket"],
            env=env, cwd=HERE, stdout=self._log,
            stderr=subprocess.STDOUT)
        say(f"server {name}: pid {self.proc.pid}, ports {self.port}/"
            f"{self.mon}, log {os.path.relpath(self.log_path, HERE)}")

    def wait_ready(self, timeout: float) -> float:
        """Seconds from spawn until /health/ready answers 200."""
        url = f"http://127.0.0.1:{self.mon}/health/ready"
        limit = time.monotonic() + min(timeout, remaining())
        while time.monotonic() < limit:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server {self.name} died during start (exit "
                    f"{self.proc.returncode}):\n{log_tail(self.log_path)}")
            try:
                with urllib.request.urlopen(url, timeout=2) as r:
                    if r.status == 200:
                        return time.monotonic() - self.t_spawn
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.5)
        raise SmokeFailure(f"server {self.name} not ready in "
                           f"{timeout:.0f}s:\n{log_tail(self.log_path)}")

    def alive(self) -> None:
        if self.proc.poll() is not None:
            raise SmokeFailure(
                f"server {self.name} died (exit {self.proc.returncode})"
                f":\n{log_tail(self.log_path)}")

    def terminate(self) -> int:
        """SIGTERM, wait for the drain, return the exit code."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=min(90.0, remaining()))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"server {self.name} ignored SIGTERM for 90s:\n"
                f"{log_tail(self.log_path)}") from None

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


# ------------------------------------------------------------------ client

async def ws_turns(http, port: int, tag: str, turns: int,
                   max_tokens: int) -> None:
    """One WebSocket session of ``turns`` user messages; every turn
    must complete with exactly ``max_tokens`` tokens and no error."""
    async with http.ws_connect(f"ws://127.0.0.1:{port}/ws/llm") as ws:
        async def recv() -> dict:
            frame = await ws.receive(timeout=300)
            if not isinstance(frame.data, str):
                raise SmokeFailure(f"{tag}: socket closed: {frame!r}")
            msg = json.loads(frame.data)
            if msg.get("type") == "error":
                raise SmokeFailure(f"{tag}: error frame {msg}")
            return msg

        msg = await recv()
        if msg["type"] != "session_started":
            raise SmokeFailure(f"{tag}: expected session_started: {msg}")
        await ws.send_json({"type": "start_session", "config": {
            "temperature": 0.7, "top_k": 40, "top_p": 0.9,
            "max_tokens": max_tokens, "ignore_eos": True}})
        msg = await recv()
        if msg["type"] != "session_configured":
            raise SmokeFailure(f"{tag}: expected session_configured: "
                               f"{msg}")
        for turn in range(turns):
            await ws.send_json({
                "type": "user_message",
                "text": f"[{tag} turn {turn}] Say something about "
                        f"systolic arrays."})
            frames = 0
            while True:
                msg = await recv()
                if msg["type"] == "token":
                    frames += 1
                elif msg["type"] == "response_complete":
                    break
            got = msg["stats"]["tokens_generated"]
            if got != max_tokens or not frames:
                raise SmokeFailure(
                    f"{tag} turn {turn}: asked {max_tokens} tokens, "
                    f"got {got} in {frames} token frames")
        await ws.send_json({"type": "end_session"})
        await recv()  # session_ended


async def sse_completion(http, port: int, model: str,
                         max_tokens: int) -> None:
    """One streaming /v1/chat/completions. The SSE stream carries no
    usage block; with ignore_eos the only way to finish is the length
    limit, so finish_reason == "length" means max_tokens were made."""
    body = {"model": model, "stream": True, "max_tokens": max_tokens,
            "ignore_eos": True,
            "messages": [{"role": "user", "content": "Hello there."}]}
    deltas, finish, done = 0, None, False
    async with http.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                         json=body) as r:
        if r.status != 200:
            raise SmokeFailure(f"/v1/chat/completions -> {r.status}: "
                               f"{(await r.text())[:300]}")
        async for raw in r.content:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                done = True
                break
            msg = json.loads(data)
            if "error" in msg:
                raise SmokeFailure(f"/v1 stream error frame: {msg}")
            choice = msg["choices"][0]
            if choice.get("delta", {}).get("content"):
                deltas += 1
            finish = choice.get("finish_reason") or finish
    if not (done and deltas and finish == "length"):
        raise SmokeFailure(f"/v1 stream: done={done} deltas={deltas} "
                           f"finish_reason={finish!r} (want 'length')")


async def drive(srv: Server, model: str) -> None:
    import aiohttp

    timeout = aiohttp.ClientTimeout(total=min(600.0, remaining()))
    async with aiohttp.ClientSession(timeout=timeout) as http:
        await ws_turns(http, srv.port, "solo", 1, 64)
        srv.alive()
        say("ok: WS turn 1 (fresh session) returned 64 tokens")
        await ws_turns(http, srv.port, "pair", 2, 64)
        srv.alive()
        say("ok: WS turns 2+3 (one session, resident KV) returned "
            "64 tokens each")
        await asyncio.gather(*(ws_turns(http, srv.port, f"burst{i}", 1, 32)
                               for i in range(16)))
        srv.alive()
        say("ok: burst of 16 concurrent sessions returned 32 tokens each")
        await sse_completion(http, srv.port, model, 32)
        srv.alive()
        say("ok: streaming /v1/chat/completions ran to its length limit")


def inspect_server(srv: Server, want_platform: str, probe: dict) -> None:
    """The server must say, in its own words, that it ran on the chip."""
    on_chip = want_platform == "tpu"  # False only under --dry-run-cpu
    info = http_json(f"http://127.0.0.1:{srv.port}/models")
    say(f"/models: {json.dumps(info)[:600]}")
    check(info.get("device", {}).get("platform") == want_platform
          and all(want_platform in d.lower() for d in info["devices"]),
          f"/models names platform {want_platform}: {info.get('devices')}")
    check(info["device"]["kind"] == probe["kind"],
          f"/models device kind {info['device']['kind']!r} is the "
          f"probe's")
    check(not on_chip or (info["num_layers"], info["hidden_size"],
                          info["vocab_size"]) == (16, 2048, 128256),
          "served llama3.2:1b at its published widths")
    mem = http_json(f"http://127.0.0.1:{srv.mon}/profiler/memory")
    say(f"/profiler/memory: {json.dumps(mem)[:400]}")
    dev0 = mem["devices"][0]
    check(dev0["platform"] == want_platform
          and (dev0["bytes_limit"] is not None or not on_chip),
          f"/profiler/memory: platform {dev0['platform']}, bytes_limit "
          f"{dev0['bytes_limit']}")
    perf = http_json(f"http://127.0.0.1:{srv.mon}/perf")
    mfu = perf.get("mfu") or {}
    check(mfu.get("device") == probe["kind"]
          and (mfu.get("peak_tflops") is not None or not on_chip),
          f"/perf: device kind {mfu.get('device')!r} with roofline peak "
          f"{mfu.get('peak_tflops')} TFLOP/s")
    metrics = http_json(f"http://127.0.0.1:{srv.mon}/metrics.json")
    check(metrics.get("engine_restarts_total") == 0,
          f"engine_restarts_total = "
          f"{metrics.get('engine_restarts_total')}")
    # Not asserted: programs that compiled under traffic (shapes the
    # fast warmup does not cover). Printed for whoever tunes warmup.
    recompiles = http_json(f"http://127.0.0.1:{srv.mon}/events"
                           f"?kind=recompile&limit=50")["events"]
    say(f"note: {len(recompiles)} serving-time compile(s): "
        + "; ".join(sorted({" ".join(
            f"{k}={v}" for k, v in e.get("attrs", {}).items())
            for e in recompiles}))[:600])


# -------------------------------------------------------------------- main

def smoke(dry_run_cpu: bool) -> dict:
    if not os.path.isfile(os.path.join(HERE, "main.py")):
        raise SmokeFailure(f"no main.py beside {__file__}: the program "
                           f"this script drives is not here")
    base_env = dict(os.environ)
    want = "tpu"
    model = "llama3.2:1b"
    kernel_args: list[str] = []
    if dry_run_cpu:
        base_env["JAX_PLATFORMS"] = "cpu"
        want, model = "cpu", "test-tiny"
        kernel_args = ["--model", "test-small", "--slots", "4",
                       "--kv-len", "256"]

    # 1. What does JAX see? (A child: this process stays off jax.)
    p = run_child("probe", [sys.executable, "-c", PROBE], base_env, 120)
    if p.returncode != 0:
        raise SmokeFailure(f"JAX found no usable device (probe exit "
                           f"{p.returncode}):\n{p.stderr[-1500:]}")
    probe = json.loads(p.stdout.strip().splitlines()[-1])
    say(f"device: platform={probe['platform']} kind={probe['kind']} "
        f"count={probe['count']} jax={probe['jax']} "
        f"jaxlib={probe['jaxlib']} libtpu={probe['libtpu']}")
    if probe["platform"] != want:
        raise SmokeFailure(
            f"no accelerator: JAX reports platform "
            f"{probe['platform']!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); this smoke "
            f"never serves on the CPU")

    # 2. The server, cold.
    env = dict(base_env, COMPUTE_DEVICE=want, LLM_PROVIDER="tpu",
               LLM_MODEL=model, ENABLE_PYDANTIC_AI="false",
               TPU_DTYPE="bfloat16", TPU_DECODE_SLOTS="16",
               TPU_MAX_MODEL_LEN="8192", TPU_WARMUP="fast",
               MODEL_PATH="", LOG_LEVEL="INFO")
    cache_dir = base_env.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(HERE, ".xla_cache")
    log_dir = os.path.join(HERE, "logs")
    os.makedirs(log_dir, exist_ok=True)
    n0 = len(cache_entries(cache_dir))
    srv = Server("cold", env, log_dir)
    try:
        cold_s = srv.wait_ready(700)
        say(f"set-up: first start ready in {cold_s:.1f}s (compile cache "
            f"{cache_dir}: {n0} entries before)")
        asyncio.run(drive(srv, model))
        inspect_server(srv, want, probe)
        check(srv.terminate() == 0, "server exited 0 on SIGTERM")
    finally:
        srv.kill()

    # 3. The same server again: the compile cache must already hold
    # everything that start needs.
    before = cache_entries(cache_dir)
    check(len(before) > 0, f"the first start left {len(before)} compile-"
          f"cache entries in {cache_dir}")
    srv = Server("warm", env, log_dir)
    try:
        warm_s = srv.wait_ready(400)
        new = cache_entries(cache_dir) - before
        say(f"set-up: second start ready in {warm_s:.1f}s (first: "
            f"{cold_s:.1f}s)")
        check(not new, f"second start added no compile-cache entries "
              f"(new: {sorted(new)[:5]})")
        check(srv.terminate() == 0, "second server exited 0 on SIGTERM")
    finally:
        srv.kill()

    # 4. Every Pallas entry point, compiled, against XLA.
    p = run_child("kernels", [sys.executable,
                              os.path.join(HERE, "scripts",
                                           "check_kernels.py"),
                              *kernel_args], base_env, 600)
    lines = p.stdout.strip().splitlines()
    for line in lines[:-1]:
        print(line[:300], flush=True)
    try:
        verdict = json.loads(lines[-1])
    except (IndexError, ValueError):
        raise SmokeFailure(f"kernel check printed no verdict (exit "
                           f"{p.returncode}):\n{p.stderr[-1500:]}") \
            from None
    check(verdict["device"]["platform"] == want
          and verdict["interpret"] is (want == "cpu"),
          f"kernel check ran on {verdict['device']} with interpret="
          f"{verdict['interpret']}")
    bad = [c["name"] for c in verdict["cases"] if not c["ok"]]
    check(p.returncode == 0 and verdict["ok"] and not bad,
          f"all {len(verdict['cases'])} Pallas cases compiled and agree "
          f"with XLA (failed: {bad})")
    return {"platform": probe["platform"], "kind": probe["kind"],
            "count": probe["count"]}


def main(argv: list[str]) -> int:
    dry_run_cpu = "--dry-run-cpu" in argv
    try:
        device = smoke(dry_run_cpu)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    say(f"all phases passed in {time.monotonic() - T0:.0f}s")
    if dry_run_cpu:
        print(json.dumps({"ok": False, "dry_run_cpu": True,
                          "device": device}), flush=True)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
