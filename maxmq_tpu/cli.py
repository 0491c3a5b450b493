"""Command-line interface: ``maxmq start`` and ``maxmq version``.

Parity surface: cmd/maxmq/main.go + internal/cli in the reference — a root
command with ``start`` (boot the broker, run until SIGINT/SIGTERM,
start.go:50-80) and ``version`` (version.go:22-33) subcommands.
"""

from __future__ import annotations

import argparse
import asyncio
import sys

from .bootstrap import (BANNER, install_event_loop,
                        new_logger_from_config, run_server)
from .utils.build import get_info
from .utils.config import load_config


def make_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="maxmq",
        description="maxmq-tpu: a TPU-native MQTT message broker")
    sub = parser.add_subparsers(dest="command")

    start = sub.add_parser("start", help="start the broker server")
    start.add_argument("--config", "-c", default=None,
                       help="path to maxmq.conf (TOML); default: search "
                            "., /etc/maxmq, /etc")
    start.add_argument("--profile", action="store_true",
                       help="write cpu.prof and heap.prof on shutdown")
    start.add_argument("--no-banner", action="store_true")

    svc = sub.add_parser(
        "matcher-service",
        help="run the chip-owning matcher service (ADR 005/006): brokers "
             "started with matcher = \"service\" connect to its socket")
    svc.add_argument("--socket", "-s", default="/tmp/maxmq-matcher.sock",
                     help="unix socket path to serve on")

    sub.add_parser("version", help="print version information")
    return parser


def cmd_version() -> int:
    print(get_info().long_version())
    return 0


def cmd_start(args: argparse.Namespace) -> int:
    conf = load_config(path=args.config)
    if args.profile:
        conf.profile = True
    logger = new_logger_from_config(conf)
    if not args.no_banner:
        print(BANNER, file=sys.stderr)
    # ADR 023 satellite: the loop policy must land before asyncio.run
    install_event_loop(conf.broker_event_loop, logger)
    try:
        asyncio.run(run_server(conf, logger))
    except KeyboardInterrupt:
        pass
    except Exception as exc:
        logger.with_prefix("bootstrap").fatal("server failed",
                                              error=str(exc))
        return 1
    # Graceful cleanup is done (broker/metrics stopped, profiles written).
    # If the accelerator runtime was initialized, skip interpreter
    # finalization: a runtime thread caught mid-compile by teardown aborts
    # the process from C++ ("exception not rethrown"). Scope the
    # workaround to that case only — a CPU-only run returns normally so
    # atexit handlers (log flushes, coverage hooks, storage plugins) fire.
    # Library callers use run_server directly and are unaffected.
    xla_bridge = sys.modules.get("jax._src.xla_bridge")
    if xla_bridge is not None and getattr(xla_bridge, "_backends", None):
        sys.stdout.flush()
        sys.stderr.flush()
        import os
        os._exit(0)
    return 0


def cmd_matcher_service(args: argparse.Namespace) -> int:
    async def run() -> None:
        from .accel import place_compile_cache
        from .matching.service import MatcherService

        place_compile_cache()
        svc = MatcherService(args.socket)
        await svc.start()
        print(f"matcher service on {args.socket}", file=sys.stderr,
              flush=True)
        try:
            await asyncio.Event().wait()
        finally:
            await svc.close()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = make_parser()
    args = parser.parse_args(argv)
    if args.command == "version":
        return cmd_version()
    if args.command == "start":
        return cmd_start(args)
    if args.command == "matcher-service":
        return cmd_matcher_service(args)
    parser.print_help()
    return 0


def main_entry() -> None:
    """console_scripts entry point (pyproject.toml: `maxmq`)."""
    sys.exit(main())


if __name__ == "__main__":
    sys.exit(main())
