"""repro.gateway — prefix-cached, multi-replica serving gateway over
``repro.engine`` (see docs/SERVING.md, "The serving gateway").

Public surface:
  Gateway / build_gateway — N engine replicas on device submeshes, prefix-
                            aware + load-aware routing with session
                            affinity, per-request token streaming
  Router                  — the routing policy (probe replicas' tries,
                            break ties by outstanding tokens)
  PrefixCache             — block-hash trie over full prompt pages with
                            ref-counted, copy-on-write page reuse in the
                            SP-sharded paged pool; leaf-first LRU eviction
  block_hashes            — the chain hash over token pages
"""

from repro.gateway.gateway import Gateway, build_gateway, replica_meshes
from repro.gateway.prefix_cache import PrefixCache, block_hashes
from repro.gateway.router import Router

__all__ = [
    "Gateway", "build_gateway", "replica_meshes",
    "PrefixCache", "block_hashes", "Router",
]
