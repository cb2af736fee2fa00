"""StarTrail core: concentric-ring sequence parallelism (the paper's contribution)."""

from repro.core.combine import combine_pair
from repro.core.ring_attention import ring_attention
from repro.core.startrail import (
    StarTrailConfig,
    decode_attention,
    sharded_startrail_attention,
    shard_positions,
    startrail_attention,
    team_positions,
)
from repro.core.topology import StarTrailTopology, valid_c_values
from repro.core.ulysses import ulysses_attention

__all__ = [
    "StarTrailConfig",
    "StarTrailTopology",
    "combine_pair",
    "decode_attention",
    "ring_attention",
    "sharded_startrail_attention",
    "shard_positions",
    "startrail_attention",
    "team_positions",
    "ulysses_attention",
    "valid_c_values",
]
