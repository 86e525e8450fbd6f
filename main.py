#!/usr/bin/env python3
"""FastTalk-TPU service CLI.

Modes (parity with the reference CLI, main.py:29-43):
  websocket  — start the WebSocket streaming service (+ monitoring port)
  config     — show resolved configuration (--show)
  test       — engine smoke test: build, generate a few tokens, exit 0/1

Overrides: --port --host --model --provider --log-level (+ --preset).
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(prog="fasttalk-tpu", description=__doc__)
    p.add_argument("mode", choices=["websocket", "config", "test"],
                   nargs="?", default="websocket")
    p.add_argument("--host")
    p.add_argument("--port", type=int)
    p.add_argument("--model")
    p.add_argument("--provider",
                   choices=["tpu", "vllm", "ollama", "fake"])
    p.add_argument("--log-level",
                   choices=["DEBUG", "INFO", "WARNING", "ERROR"])
    p.add_argument("--preset", choices=["fast", "balanced", "quality"])
    p.add_argument("--show", action="store_true",
                   help="config mode: print resolved settings")
    return p.parse_args(argv)


def apply_overrides(args: argparse.Namespace) -> None:
    """CLI flags become env vars before Config resolves (reference:
    main.py:49-61)."""
    if args.host:
        os.environ["LLM_HOST"] = args.host
    if args.port:
        os.environ["LLM_PORT"] = str(args.port)
    if args.model:
        os.environ["LLM_MODEL"] = args.model
    if args.provider:
        os.environ["LLM_PROVIDER"] = args.provider
    if args.log_level:
        os.environ["LOG_LEVEL"] = args.log_level


def run_config(args: argparse.Namespace) -> int:
    import json

    from fasttalk_tpu.utils.config import Config

    cfg = Config()
    if args.preset:
        cfg.apply_preset(args.preset)
    print(json.dumps(cfg.to_dict(), indent=2, default=str))
    return 0


def run_test(args: argparse.Namespace) -> int:
    """Engine connectivity/diagnostic test (reference: main.py:93-197
    probed external backends; here the engine is in-process, so the test
    builds it and generates real tokens)."""
    from fasttalk_tpu.engine.engine import GenerationParams
    from fasttalk_tpu.engine.factory import build_engine
    from fasttalk_tpu.utils.config import Config
    from fasttalk_tpu.utils.logger import configure_logging, get_logger

    cfg = Config()
    configure_logging(cfg.log_level)
    log = get_logger("main.test")
    log.info(f"Building engine: provider={cfg.llm_provider} "
             f"model={cfg.model_name} device={cfg.compute_device}")
    try:
        engine = build_engine(cfg)
        engine.start()
        if not engine.check_connection():
            log.error("Engine failed connectivity check")
            return 1
        info = engine.get_model_info()
        log.info(f"Engine ready: {info}")

        async def probe() -> int:
            n = 0
            async for ev in engine.generate(
                    "selftest", "selftest",
                    [{"role": "user", "content": "Hello!"}],
                    GenerationParams(max_tokens=8, temperature=0.0,
                                     top_k=0, top_p=1.0)):
                if ev["type"] == "token":
                    n += 1
                if ev["type"] == "error":
                    raise RuntimeError(ev.get("error"))
            return n

        chunks = asyncio.run(probe())
        log.info(f"Generated {chunks} stream chunks — engine OK")
        engine.shutdown()
        print("OK")
        return 0
    except Exception as e:
        log.error(f"Engine test failed: {e}", exc_info=True)
        print("FAILED")
        return 1


def run_websocket(args: argparse.Namespace) -> int:
    from fasttalk_tpu.serving.launcher import ServerLauncher
    from fasttalk_tpu.utils.config import Config
    from fasttalk_tpu.utils.logger import configure_logging, get_logger

    cfg = Config()
    if args.preset:
        cfg.apply_preset(args.preset)
    configure_logging(cfg.log_level, log_path=cfg.log_path or None)
    log = get_logger("main")
    log.info(f"Starting FastTalk-TPU: provider={cfg.llm_provider} "
             f"model={cfg.model_name} device={cfg.compute_device} "
             f"port={cfg.port} monitoring={cfg.monitoring_port}")
    if cfg.spmd_role == "follower":
        from fasttalk_tpu.serving.launcher import run_spmd_follower

        return run_spmd_follower(cfg)
    ServerLauncher(cfg).start()
    return 0


def main(argv: list[str] | None = None) -> int:
    args = parse_args(argv)
    apply_overrides(args)
    if args.mode == "config":
        return run_config(args)
    if args.mode == "test":
        return run_test(args)
    return run_websocket(args)


if __name__ == "__main__":
    sys.exit(main())
