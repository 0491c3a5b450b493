"""Tests for the operational shell: snowflake IDs, logger, config, build.

Models the reference's per-package unit tests (internal/snowflake/
snowflake_test.go, internal/logger/logger_test.go, internal/config/
config_test.go, internal/build)."""

from __future__ import annotations

import io
import json
import threading

import pytest

from maxmq_tpu.utils import build as build_info
from maxmq_tpu.utils.config import (Config, config_as_dict, load_config,
                                    read_config_file)
from maxmq_tpu.utils.logger import (DEBUG, INFO, Logger, new_logger,
                                    set_severity_level)
from maxmq_tpu.utils.snowflake import (EPOCH_MS, MAX_MACHINE_ID, Snowflake)


# ---------------------------------------------------------------- snowflake

class TestSnowflake:
    def test_bit_layout(self):
        sf = Snowflake(machine_id=513)
        id_ = sf.next_id()
        assert Snowflake.machine_of(id_) == 513
        assert Snowflake.sequence_of(id_) < 4096
        import time
        now_ms = time.time_ns() // 1_000_000
        assert abs(Snowflake.timestamp_ms(id_) - now_ms) < 5_000
        assert Snowflake.timestamp_ms(id_) > EPOCH_MS

    def test_machine_id_bounds(self):
        with pytest.raises(ValueError):
            Snowflake(machine_id=-1)
        with pytest.raises(ValueError):
            Snowflake(machine_id=MAX_MACHINE_ID + 1)
        Snowflake(machine_id=MAX_MACHINE_ID)  # ok

    def test_uniqueness_and_monotonic(self):
        sf = Snowflake()
        ids = [sf.next_id() for _ in range(10_000)]
        assert len(set(ids)) == len(ids)
        assert ids == sorted(ids)

    def test_concurrent_uniqueness(self):
        sf = Snowflake(machine_id=7)
        out: list[int] = []
        lock = threading.Lock()

        def worker():
            local = [sf.next_id() for _ in range(2000)]
            with lock:
                out.extend(local)

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(set(out)) == len(out)


# ------------------------------------------------------------------- logger

class TestLogger:
    def test_json_format_fields(self):
        buf = io.StringIO()
        log = new_logger(fmt="json", level="debug", out=buf,
                         log_id_gen=lambda: 42)
        log.info("hello", client="abc", n=3)
        event = json.loads(buf.getvalue())
        assert event["message"] == "hello"
        assert event["level"] == "info"
        assert event["client"] == "abc"
        assert event["n"] == 3
        assert event["log_id"] == 42
        assert isinstance(event["time"], int)

    def test_severity_filtering(self):
        buf = io.StringIO()
        log = new_logger(fmt="json", level="warn", out=buf)
        log.info("dropped")
        log.debug("dropped")
        log.warn("kept")
        lines = [l for l in buf.getvalue().splitlines() if l]
        assert len(lines) == 1
        assert json.loads(lines[0])["message"] == "kept"
        set_severity_level(INFO)

    def test_prefix_chaining(self):
        buf = io.StringIO()
        set_severity_level(DEBUG)
        log = Logger(out=buf, fmt="json", prefix="bootstrap")
        child = log.with_prefix("mqtt")
        child.info("x")
        assert json.loads(buf.getvalue())["prefix"] == "bootstrap.mqtt"
        set_severity_level(INFO)

    def test_pretty_format(self):
        buf = io.StringIO()
        log = Logger(out=buf, fmt="pretty", prefix="mqtt", color=False)
        log.info("client connected", id="c1")
        line = buf.getvalue()
        assert "INF" in line
        assert "[mqtt]" in line
        assert "client connected" in line
        assert "id=c1" in line

    def test_bad_format_rejected(self):
        with pytest.raises(ValueError):
            Logger(fmt="xml")
        with pytest.raises(ValueError):
            new_logger(level="loud")


# ------------------------------------------------------------------- config

class TestConfig:
    def test_defaults(self):
        conf = Config()
        assert conf.mqtt_tcp_address == ":1883"
        assert conf.metrics_address == ":8888"
        assert conf.log_level == "info"
        assert conf.mqtt_max_qos == 2
        assert conf.matcher == "sig"

    def test_toml_file(self, tmp_path):
        p = tmp_path / "maxmq.conf"
        p.write_text('log_level = "debug"\nmqtt_max_qos = 1\n'
                     'metrics_enabled = false\n')
        conf = load_config(path=str(p), env={})
        assert conf.log_level == "debug"
        assert conf.mqtt_max_qos == 1
        assert conf.metrics_enabled is False
        assert conf.mqtt_tcp_address == ":1883"  # default preserved

    def test_env_overrides_file(self, tmp_path):
        p = tmp_path / "maxmq.conf"
        p.write_text('log_level = "debug"\n')
        conf = load_config(path=str(p), env={
            "MAXMQ_LOG_LEVEL": "error",
            "MAXMQ_MQTT_MAX_INFLIGHT_MESSAGES": "77",
            "MAXMQ_METRICS_PROFILING": "true",
            "MAXMQ_MQTT_RETAIN_AVAILABLE": "0",
        })
        assert conf.log_level == "error"
        assert conf.mqtt_max_inflight_messages == 77
        assert conf.metrics_profiling is True
        assert conf.mqtt_retain_available is False

    def test_reference_key_aliases(self, tmp_path):
        # a maxmq.conf written for the reference drops in unchanged
        # (internal/config/config.go:27-94 spellings)
        p = tmp_path / "maxmq.conf"
        p.write_text(
            "mqtt_max_session_expiry_interval = 7200\n"
            "mqtt_max_outbound_messages = 4096\n"
            "mqtt_subscription_identifier_available = false\n"
            "mqtt_sys_topic_update_interval = 9\n"
            "mqtt_shutdown_timeout = 7\n"
            "mqtt_buffer_size = 2048\n"
            "mqtt_min_protocol_version = 4\n")
        conf = load_config(path=str(p), env={})
        assert conf.mqtt_session_expiry_interval == 7200
        assert conf.mqtt_max_outbound_queue == 4096
        assert conf.mqtt_subscription_id_available is False
        assert conf.mqtt_sys_topic_interval == 9
        assert conf.mqtt_shutdown_timeout == 7
        assert conf.mqtt_buffer_size == 2048
        assert conf.mqtt_min_protocol_version == 4
        # env spelling aliases too
        conf = load_config(path=str(p), env={
            "MAXMQ_MQTT_SYS_TOPIC_UPDATE_INTERVAL": "3"})
        assert conf.mqtt_sys_topic_interval == 3

    def test_missing_file_ok(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        assert read_config_file() == {}
        conf = load_config(env={})
        assert conf.mqtt_tcp_address == ":1883"

    def test_as_dict_round_trip(self):
        d = config_as_dict(Config())
        assert d["matcher"] == "sig"
        assert "mqtt_max_topic_alias" in d


# ----------------------------------------------------------------- build

class TestBuildInfo:
    def test_info(self):
        info = build_info.get_info()
        assert info.version
        assert info.short_version() == info.version
        assert info.distribution in info.long_version()


def test_module_entrypoint_version():
    """python -m maxmq_tpu version (covers __main__.py + cli version)."""
    import subprocess
    import sys

    p = subprocess.run([sys.executable, "-m", "maxmq_tpu", "version"],
                       cwd="/root/repo", capture_output=True, text=True,
                       timeout=60)
    assert p.returncode == 0
    assert "maxmq" in p.stdout.lower() or "0." in p.stdout


def test_cli_start_bad_address_exits_nonzero(tmp_path):
    import os
    import subprocess
    import sys

    conf = tmp_path / "bad.conf"
    conf.write_text('mqtt_tcp_address = "256.0.0.1:99999"\n'
                    'matcher = "trie"\n')
    env = dict(os.environ, JAX_PLATFORMS="cpu")   # no accelerator init
    p = subprocess.run(
        [sys.executable, "-m", "maxmq_tpu", "start", "--config",
         str(conf), "--no-banner"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1
