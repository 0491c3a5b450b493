"""Tests for the CLI and the full bootstrap path.

Models the reference's internal/cli tests — version_test.go (version output)
and start_test.go:31-73, which runs the whole runServer in-process with a
cancellable context and asserts the profile files are written."""

from __future__ import annotations

import asyncio
import urllib.request

import pytest

from maxmq_tpu.bootstrap import (build_broker, capabilities_from_config,
                                 run_server)
from maxmq_tpu.cli import main, make_parser
from maxmq_tpu.matching.batcher import MicroBatcher
from maxmq_tpu.mqtt_client import MQTTClient
from maxmq_tpu.utils.build import get_info
from maxmq_tpu.utils.config import Config
from maxmq_tpu.utils.logger import Logger


def quiet_logger():
    import io
    return Logger(out=io.StringIO(), fmt="json")


class TestCLI:
    def test_version_command(self, capsys):
        assert main(["version"]) == 0
        out = capsys.readouterr().out
        assert get_info().version in out

    def test_no_command_prints_help(self, capsys):
        assert main([]) == 0
        assert "start" in capsys.readouterr().out

    def test_parser_start_flags(self):
        args = make_parser().parse_args(
            ["start", "--config", "/tmp/x.conf", "--profile"])
        assert args.command == "start"
        assert args.config == "/tmp/x.conf"
        assert args.profile is True


class TestConfigMapping:
    def test_capabilities_from_config(self):
        conf = Config(mqtt_max_qos=1, mqtt_retain_available=False,
                      mqtt_max_inflight_messages=9)
        caps = capabilities_from_config(conf)
        assert caps.maximum_qos == 1
        assert caps.retain_available is False
        assert caps.maximum_inflight == 9

    def test_build_broker_listeners_and_matcher(self):
        conf = Config(mqtt_tcp_address="127.0.0.1:0",
                      mqtt_sys_http_address="127.0.0.1:0",
                      matcher="trie", storage_backend="memory")
        broker = build_broker(conf, quiet_logger())
        assert broker.listeners.get("tcp") is not None
        assert broker.listeners.get("sys-http") is not None
        assert broker.matcher is None  # trie = built-in CPU path
        assert len(broker.hooks) == 3  # logging + allow + storage

    def test_build_broker_sig_matcher_is_batched(self):
        from maxmq_tpu.matching.supervisor import SupervisedMatcher

        conf = Config(mqtt_tcp_address="", metrics_enabled=False,
                      matcher="sig", matcher_max_levels=8)
        broker = build_broker(conf, quiet_logger())
        # ADR 011: the batcher ships wrapped in the degradation ladder
        assert isinstance(broker.matcher, SupervisedMatcher)
        assert isinstance(broker.matcher.inner, MicroBatcher)
        assert broker.matcher.index is broker.topics
        assert broker.matcher.engine.max_levels == 8

    def test_build_broker_matcher_supervision_opt_out(self):
        conf = Config(mqtt_tcp_address="", metrics_enabled=False,
                      matcher="sig", matcher_max_levels=8,
                      matcher_supervised=False)
        broker = build_broker(conf, quiet_logger())
        assert isinstance(broker.matcher, MicroBatcher)

    @pytest.mark.parametrize("matcher", ["nfa", "dense"])
    def test_deleted_engines_are_refused_by_name(self, matcher):
        """``nfa`` and ``dense`` named engines that are gone: a file that
        still asks for one fails at boot, told what exists."""
        conf = Config(mqtt_tcp_address="", metrics_enabled=False,
                      matcher=matcher)
        with pytest.raises(ValueError, match=r"trie\|sig\|service") as err:
            build_broker(conf, quiet_logger())
        assert repr(matcher) in str(err.value)


class TestAccelPolicy:
    def test_cpu_fallback_refused_unless_asked_for(self):
        """JAX on a CPU nobody named is a chip that failed to come up:
        the device engines refuse it. Naming the CPU (JAX_PLATFORMS, or
        the pin in conftest.py) is how tests and harnesses run."""
        import jax
        import pytest

        from maxmq_tpu.accel import require_accelerator
        from maxmq_tpu.matching.service import MatcherService

        require_accelerator("test")             # conftest pinned the CPU
        conf = Config(mqtt_tcp_address="", metrics_enabled=False,
                      matcher="sig")
        pinned = jax.config.jax_platforms
        jax.config.update("jax_platforms", "")  # as if nobody had asked
        try:
            with pytest.raises(RuntimeError, match="no accelerator"):
                build_broker(conf, quiet_logger())
            with pytest.raises(RuntimeError, match="no accelerator"):
                MatcherService("/unused")._factory(None)
            conf.matcher = "trie"               # needs no device at all
            assert build_broker(conf, quiet_logger()).matcher is None
        finally:
            jax.config.update("jax_platforms", pinned)

    def test_compile_cache_placed_from_outside_or_in_checkout(
            self, monkeypatch):
        import os

        import jax

        from maxmq_tpu import accel

        before = (jax.config.jax_compilation_cache_dir,
                  jax.config.jax_persistent_cache_min_compile_time_secs,
                  jax.config.jax_include_full_tracebacks_in_locations)
        try:
            monkeypatch.setenv(accel.CACHE_ENV, "/some/dir")
            assert accel.place_compile_cache() == "/some/dir"
            # JAX reads the variable itself: no directory is set in code
            assert jax.config.jax_compilation_cache_dir == before[0]
            # a kernel program's key must not move with callers' lines
            assert not jax.config.jax_include_full_tracebacks_in_locations
            monkeypatch.delenv(accel.CACHE_ENV)
            repo = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            assert accel.place_compile_cache() == os.path.join(
                repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == os.path.join(
                repo, ".jax_cache")
        finally:
            jax.config.update("jax_compilation_cache_dir", before[0])
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", before[1])
            jax.config.update(
                "jax_include_full_tracebacks_in_locations", before[2])


async def test_run_server_end_to_end(tmp_path, monkeypatch):
    """Full boot: config → broker + metrics; a real client connects and does
    a QoS0 roundtrip; metrics scrape sees it; clean shutdown; profiles
    written (start_test.go:31-73 analogue)."""
    monkeypatch.chdir(tmp_path)
    conf = Config(mqtt_tcp_address="127.0.0.1:18831",
                  metrics_address="127.0.0.1:18832",
                  metrics_profiling=False, matcher="trie",
                  mqtt_sys_topic_interval=0,
                  profile=True, profile_path=str(tmp_path))
    ready, stop = asyncio.Event(), asyncio.Event()
    task = asyncio.create_task(
        run_server(conf, quiet_logger(), ready=ready, stop=stop))
    await asyncio.wait_for(ready.wait(), timeout=10)

    c = MQTTClient(client_id="boot-c1")
    await c.connect("127.0.0.1", 18831)
    await c.subscribe(("boot/#", 0))
    await c.publish("boot/x", b"hello")
    msg = await c.next_message(timeout=5)
    assert msg.payload == b"hello"
    await c.disconnect()

    def fetch():
        with urllib.request.urlopen(
                "http://127.0.0.1:18832/metrics") as r:
            return r.read().decode()
    text = await asyncio.get_running_loop().run_in_executor(None, fetch)
    assert "maxmq_mqtt_messages_received 1" in text

    stop.set()
    await asyncio.wait_for(task, timeout=10)
    assert (tmp_path / "cpu.prof").exists()
    assert (tmp_path / "heap.prof").exists()


async def test_run_server_cluster_mesh_matcher():
    """Config-driven cluster mode: ``matcher_mesh = "2x4"`` boots a
    ShardedSigEngine (intents on, ADR 007) behind the micro-batcher on
    the 8-virtual-device mesh, and a live client round-trips through
    the sharded match path."""
    from maxmq_tpu.parallel.sharded import ShardedSigEngine

    conf = Config(mqtt_tcp_address="127.0.0.1:18833",
                  metrics_enabled=False, matcher="sig",
                  matcher_mesh="2x4", matcher_batch_window_us=0,
                  mqtt_sys_topic_interval=0)
    ready, stop = asyncio.Event(), asyncio.Event()
    task = asyncio.create_task(
        run_server(conf, quiet_logger(), ready=ready, stop=stop,
                   broker_out=(captured := [])))
    try:
        await asyncio.wait_for(ready.wait(), timeout=90)
        broker = captured[0]
        eng = broker.matcher.engine
        assert isinstance(eng, ShardedSigEngine), eng
        assert eng.emit_intents is True                # ADR 007 default

        c = MQTTClient(client_id="mesh-c1")
        await c.connect("127.0.0.1", 18833)
        await c.subscribe(("mesh/+/t", 1))
        await c.publish("mesh/a/t", b"sharded", qos=1)
        msg = await c.next_message(timeout=20)
        assert (msg.payload, msg.topic) == (b"sharded", "mesh/a/t")
        await c.disconnect()
    finally:
        stop.set()
    await asyncio.wait_for(task, timeout=15)


test_run_server_cluster_mesh_matcher._async_timeout = 150
