"""maxmq-tpu: a TPU-native MQTT messaging framework.

Host-side asyncio broker runtime (protocol codec, sessions, QoS flows, hooks,
observability) with the topic->subscriber matching hot path compiled to
grouped signature tables matched in batch on TPU via JAX/Pallas
(matching/sig.py), sharded across a device mesh for cluster mode.
"""

__version__ = "0.1.0"
