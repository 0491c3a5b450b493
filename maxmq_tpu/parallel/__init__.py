"""Mesh-sharded (cluster-mode) matching: subscriptions partitioned into
per-device signature tables over a ('data', 'subs') mesh; matched row ids
are reassembled across shards over the ICI."""

from .sharded import ShardedSigEngine, make_mesh

__all__ = ["ShardedSigEngine", "make_mesh"]
