"""Tests for the metrics server, the maxmq_mqtt_* Prometheus bridge, the
logging hook, and the $SYS HTTP stats listener.

Models internal/metrics/server_test.go (constructor validation, bad address,
start/stop, scrape) and internal/mqtt/logging_test.go (log output per hook
event) in the reference."""

from __future__ import annotations

import io
import json
import urllib.request

import pytest

from maxmq_tpu.broker import Broker, BrokerOptions, Capabilities
from maxmq_tpu.broker.listeners import HTTPStatsListener
from maxmq_tpu.hooks.logging import LoggingHook
from maxmq_tpu.metrics import (MetricsServer, Registry,
                               register_broker_metrics)
from maxmq_tpu.protocol.codec import FixedHeader, PacketType
from maxmq_tpu.protocol.packets import Packet, Subscription
from maxmq_tpu.utils.logger import Logger, set_severity_level


def scrape(port: int, path: str = "/metrics") -> tuple[int, str]:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
        return r.status, r.read().decode()


class TestRegistry:
    def test_exposition_format(self):
        reg = Registry()
        reg.counter_func("test_total", "A counter.", lambda: 41)
        reg.gauge_func("test_now", "A gauge.", lambda: 1.5,
                       labels={"kind": "x"})
        text = reg.expose()
        assert "# HELP test_total A counter." in text
        assert "# TYPE test_total counter" in text
        assert "test_total 41" in text
        assert 'test_now{kind="x"} 1.5' in text

    def test_failing_metric_skipped(self):
        reg = Registry()

        def boom():
            raise RuntimeError

        reg.gauge_func("bad", "x", boom)
        reg.gauge_func("good", "x", lambda: 2)
        text = reg.expose()
        assert "good 2" in text
        assert not any(line.startswith("bad ")
                       for line in text.splitlines())


class TestMetricsServer:
    def test_invalid_address(self):
        with pytest.raises(ValueError):
            MetricsServer("no-port", Registry())

    def test_scrape_and_stop(self):
        reg = Registry()
        reg.gauge_func("up", "Server is up.", lambda: 1)
        srv = MetricsServer("127.0.0.1:0", reg)
        srv.start()
        try:
            status, text = scrape(srv.bound_port)
            assert status == 200
            assert "up 1" in text
            with pytest.raises(Exception):
                scrape(srv.bound_port, "/nope")
        finally:
            srv.stop()

    def test_profiling_endpoints(self):
        srv = MetricsServer("127.0.0.1:0", Registry(), profiling=True)
        srv.start()
        try:
            status, text = scrape(srv.bound_port, "/debug/pprof/threads")
            assert status == 200
            assert "Thread" in text or "File" in text
            status, _ = scrape(srv.bound_port, "/debug/pprof/heap")
            assert status == 200
        finally:
            srv.stop()

    def test_profiling_disabled_404(self):
        srv = MetricsServer("127.0.0.1:0", Registry(), profiling=False)
        srv.start()
        try:
            with pytest.raises(urllib.error.HTTPError):
                scrape(srv.bound_port, "/debug/pprof/threads")
        finally:
            srv.stop()


class TestBrokerBridge:
    def test_registers_mqtt_metrics(self):
        broker = Broker(BrokerOptions(capabilities=Capabilities()))
        broker.info.messages_received = 5
        broker.info.clients_connected = 2
        reg = Registry()
        register_broker_metrics(reg, broker)
        text = reg.expose()
        assert "maxmq_mqtt_messages_received 5" in text
        assert "maxmq_mqtt_clients_connected 2" in text
        # live read at scrape time, not registration time
        broker.info.messages_received = 9
        assert "maxmq_mqtt_messages_received 9" in reg.expose()


class _FakeClient:
    id = "cl1"
    listener = "t1"
    remote = "127.0.0.1:1"
    keepalive = 60
    inflight = ()


def _publish(topic="a/b", qos=0):
    p = Packet(fixed=FixedHeader(type=PacketType.PUBLISH, qos=qos))
    p.topic = topic
    p.payload = b"hi"
    return p


class TestLoggingHook:
    def _hook(self) -> tuple[LoggingHook, io.StringIO]:
        buf = io.StringIO()
        set_severity_level("trace")
        hook = LoggingHook(Logger(out=buf, fmt="json"))
        return hook, buf

    def _events(self, buf) -> list[dict]:
        return [json.loads(line) for line in buf.getvalue().splitlines()]

    def test_lifecycle_and_publish_events(self):
        hook, buf = self._hook()
        hook.on_started()
        hook.on_publish(_publish(), _FakeClient())
        hook.on_publish_dropped(_FakeClient(), _publish())
        hook.on_stopped()
        set_severity_level("info")
        events = self._events(buf)
        assert [e["message"] for e in events] == [
            "broker started", "received PUBLISH",
            "publish dropped (slow consumer)", "broker stopped"]
        assert events[1]["topic"] == "a/b"
        assert events[2]["level"] == "warn"

    def test_packet_read_is_modify_passthrough(self):
        hook, buf = self._hook()
        p = _publish()
        assert hook.on_packet_read(p, _FakeClient()) is p
        set_severity_level("info")
        event = self._events(buf)[0]
        assert event["type"] == "PUBLISH"
        assert event["level"] == "trace"

    def test_subscribe_events(self):
        hook, buf = self._hook()
        p = Packet(fixed=FixedHeader(type=PacketType.SUBSCRIBE))
        p.filters = [Subscription(filter="a/+", qos=1)]
        hook.on_subscribed(_FakeClient(), p, [1], [1])
        hook.on_unsubscribed(_FakeClient(), p)
        set_severity_level("info")
        events = self._events(buf)
        assert events[0]["filters"] == ["a/+"]
        assert events[1]["message"] == "client unsubscribed"


async def test_http_stats_listener():
    broker = Broker(BrokerOptions(capabilities=Capabilities(
        sys_topic_interval=0)))
    from maxmq_tpu.hooks import AllowHook
    broker.add_hook(AllowHook())
    listener = broker.add_listener(
        HTTPStatsListener("stats", "127.0.0.1:0", lambda: broker.info))
    await broker.serve()
    try:
        port = listener._server.sockets[0].getsockname()[1]
        import asyncio
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"GET /sys HTTP/1.1\r\nHost: x\r\n\r\n")
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(), timeout=5)
        head, _, body = raw.partition(b"\r\n\r\n")
        assert b"200 OK" in head
        data = json.loads(body)
        assert data["version"] == broker.info.version
        assert "clients_connected" in data
    finally:
        await broker.close()


def test_matcher_metrics_series_render():
    """The ADR-007/008 matcher series (bypass, trie-route, RTT) appear
    in the exposition when a batcher-wrapped engine is attached."""
    from maxmq_tpu.matching.batcher import MicroBatcher
    from maxmq_tpu.matching.sig import SigEngine

    broker = Broker(BrokerOptions(
        capabilities=Capabilities(sys_topic_interval=0)))
    broker.topics.subscribe("m1", Subscription(filter="mx/+", qos=0))
    eng = SigEngine(broker.topics)
    mb = MicroBatcher(eng)
    broker.attach_matcher(mb)
    mb.bypasses = 3
    mb._device_rtt = 0.012   # seed the EWMA the property exposes
    eng.trie_routed = 5
    eng.host_matches, eng.host_fused_batches = 7, 4
    reg = Registry()
    register_broker_metrics(reg, broker)
    text = reg.expose()
    assert "maxmq_matcher_matches_total" in text
    assert "maxmq_matcher_bypassed_topics_total 3" in text
    assert "maxmq_matcher_device_rtt_seconds 0.012" in text
    assert "maxmq_matcher_trie_routed_total 5" in text
    assert "maxmq_matcher_host_matches_total 7" in text
    assert "maxmq_matcher_host_fused_batches_total 4" in text


def test_round_trip_gauges_are_named_apart_and_phases_exposed():
    """The loop-side estimate keeps its name and says what it is; the
    time taken on the executor thread has a gauge of its own; a traced
    batch's phases feed a histogram family whose ladder starts at
    10 us. The exposition stays conformant."""
    import importlib.util
    import os
    from maxmq_tpu.matching.batcher import MicroBatcher
    from maxmq_tpu.matching.sig import SigEngine
    from maxmq_tpu.matching.supervisor import SupervisedMatcher

    broker = Broker(BrokerOptions(capabilities=Capabilities(
        sys_topic_interval=0, trace_sample_n=1)))
    mb = MicroBatcher(SigEngine(broker.topics))
    mb.tracer = broker.tracer
    broker.attach_matcher(SupervisedMatcher(mb, index=broker.topics))
    mb._device_rtt, mb.device_round_trip = 0.011, 0.0004
    rec = broker.tracer.open_batch(3)
    rec.phase("match_prep", 0, 30_000)          # 30 us
    reg = Registry()
    register_broker_metrics(reg, broker)
    text = reg.expose()
    assert "maxmq_matcher_device_rtt_seconds 0.011" in text
    assert "maxmq_matcher_device_round_trip_seconds 0.0004" in text
    assert "executor hops included; drives the bypass" in text
    fam = "maxmq_matcher_batch_phase_seconds"
    assert f'{fam}_bucket{{phase="match_prep",le="1e-05"}} 0' in text
    assert f'{fam}_bucket{{phase="match_prep",le="5e-05"}} 1' in text
    assert f'{fam}_count{{phase="match_hop"}} 0' in text
    spec = importlib.util.spec_from_file_location(
        "_expo_check", os.path.join(os.path.dirname(__file__), os.pardir,
                                    "scripts",
                                    "check_metrics_exposition.py"))
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    assert checker.validate(text) == []


def test_kernel_width_metrics_render():
    """The ADR-010 dual-width kernel series reflect the LIVE plan at
    scrape time (groups/words by width, plane passes saved)."""
    from maxmq_tpu.matching.batcher import MicroBatcher
    from maxmq_tpu.matching.sig import SigEngine

    broker = Broker(BrokerOptions(
        capabilities=Capabilities(sys_topic_interval=0)))
    for i in range(3):
        broker.topics.subscribe(f"k{i}",
                                Subscription(filter=f"kw/{i}/#", qos=0))
    eng = SigEngine(broker.topics)
    broker.attach_matcher(MicroBatcher(eng))
    reg = Registry()
    register_broker_metrics(reg, broker)
    text = reg.expose()
    assert 'maxmq_matcher_kernel_groups{width="16"}' in text
    assert 'maxmq_matcher_kernel_groups{width="32"}' in text
    assert 'maxmq_matcher_kernel_words{width="16"}' in text
    assert "maxmq_matcher_kernel_plane_passes_saved_per_topic" in text
    if eng.kernel_plan is not None:     # pallas plan admitted the tables
        g16 = eng.kernel_plan["groups16"]
        assert f'maxmq_matcher_kernel_groups{{width="16"}} {g16}' in text
