"""A Sparkplug B edge node's session life cycle (Eclipse Sparkplug 3.0.0,
ch. 5) through a served broker over TCP: the part of the source that the
benchmark's cell ``sparkplug-plant.steady`` cannot drive, because its load
generator speaks no will, retain flag or SUBSCRIBE. The broker is
``bootstrap.run_server``'s, with ``matcher = "sig"`` over a restored plant
at the rehearsal's size, so every publish below crosses the listener, the
micro-batcher and the engine."""

from __future__ import annotations

import asyncio
import io

from maxmq_tpu.bootstrap import run_server
from maxmq_tpu.hooks.storage import SQLiteStore, SubscriptionRecord
from maxmq_tpu.mqtt_client import MQTTClient
from maxmq_tpu.protocol import Will
from maxmq_tpu.utils.config import Config
from maxmq_tpu.utils.logger import Logger

from test_sparkplug_plant import SEED, recipes


async def node(port: int, group: str, name: str, state: str) -> MQTTClient:
    """An edge node as the specification has it connect: clean session,
    NDEATH as its will at QoS 1, then its three subscriptions."""
    c = MQTTClient(client_id=f"sp-edge-{group}-{name}", clean_start=True,
                   will=Will(topic=f"spBv1.0/{group}/NDEATH/{name}",
                             payload=b"bdSeq=0", qos=1))
    await c.connect("127.0.0.1", port)
    granted = await c.subscribe((f"spBv1.0/{group}/NCMD/{name}/#", 1),
                                (f"spBv1.0/{group}/DCMD/{name}/#", 1),
                                (state, 1))
    assert granted == [1, 1, 1]
    return c


async def test_edge_node_life_cycle_through_a_served_broker(tmp_path):
    stored, _plan, _hits = recipes()
    state = stored[2]
    store = SQLiteStore(str(tmp_path / "store.db"), synchronous="OFF")
    store.apply_batch([
        ("put", "subscriptions", f"cl-{i}|{f}",
         SubscriptionRecord(client_id=f"cl-{i}", filter=f, qos=1).to_json())
        for i, f in enumerate(stored)])
    store.close()
    conf = Config(mqtt_tcp_address="127.0.0.1:0", metrics_enabled=False,
                  matcher="sig", mqtt_sys_topic_interval=0, log_level="warn",
                  storage_backend="sqlite",
                  storage_path=str(tmp_path / "store.db"))
    ready, stop, built = asyncio.Event(), asyncio.Event(), []
    server = asyncio.ensure_future(run_server(
        conf, Logger(out=io.StringIO(), fmt="json"), ready=ready, stop=stop,
        broker_out=built))
    clients: list[MQTTClient] = []
    try:
        await asyncio.wait_for(ready.wait(), timeout=120)
        broker = built[0]
        assert broker.topics.subscription_count == len(stored)
        assert broker.matcher is not None
        port = broker.listeners.get("tcp")._server.sockets[0] \
            .getsockname()[1]

        host = MQTTClient(client_id="sp-host-primary", clean_start=True)
        clients.append(host)
        await host.connect("127.0.0.1", port)
        await host.subscribe(("spBv1.0/#", 1))
        # the primary host announces itself: STATE is QoS 1, retained
        await host.publish(state, b'{"online":true}', qos=1, retain=True)
        echo = await host.next_message()
        assert (echo.topic, echo.retain) == (state, False)

        group, name = "press-00aa", "line-0001a"
        edge = await node(port, group, name, state)
        other = await node(port, group, "line-0002b", state)
        clients += [edge, other]
        # an edge node that subscribes later still learns the host is up
        for c in (edge, other):
            msg = await c.next_message()
            assert (msg.topic, msg.payload, msg.retain, msg.qos) == \
                (state, b'{"online":true}', True, 1)

        # NBIRTH, DBIRTH, then data by exception, all QoS 0: the host on
        # spBv1.0/# sees them in the order the node sent them
        sent = [(f"spBv1.0/{group}/NBIRTH/{name}", b"bdSeq=0"),
                (f"spBv1.0/{group}/DBIRTH/{name}/d00", b"metrics")]
        sent += [(f"spBv1.0/{group}/DDATA/{name}/d00", b"seq=%d" % k)
                 for k in range(40)]
        sent.append((f"spBv1.0/{group}/NDATA/{name}", b"seq=40"))
        for topic, payload in sent:
            await edge.publish(topic, payload)
        got = [await host.next_message(timeout=30) for _ in sent]
        assert [(m.topic, m.payload) for m in got] == sent
        assert all(m.qos == 0 and not m.retain for m in got)

        # a command reaches its node alone (and the host's own wildcard)
        await host.publish(f"spBv1.0/{group}/NCMD/{name}", b"rebirth")
        await host.publish(f"spBv1.0/{group}/DCMD/{name}/d00", b"set")
        cmds = [await edge.next_message(timeout=30) for _ in range(2)]
        assert [(m.topic, m.payload) for m in cmds] == [
            (f"spBv1.0/{group}/NCMD/{name}", b"rebirth"),
            (f"spBv1.0/{group}/DCMD/{name}/d00", b"set")]
        for _ in range(2):
            await host.next_message(timeout=30)
        assert other.messages.empty() and host.messages.empty()

        # the segment is lost: the socket is cut with no DISCONNECT, and
        # the host receives the node's NDEATH at the will's QoS
        await edge.close()
        death = await host.next_message(timeout=30)
        assert (death.topic, death.payload, death.qos) == \
            (f"spBv1.0/{group}/NDEATH/{name}", b"bdSeq=0", 1)
        assert other.messages.empty()
        # no answer came from a path that failed
        sup = broker.matcher
        assert (sup.error_fallbacks, broker.matcher_degrades) == (0, 0)
    finally:
        for c in clients:
            await c.close()
        stop.set()
        await asyncio.wait_for(server, timeout=120)


test_edge_node_life_cycle_through_a_served_broker._async_timeout = 300
