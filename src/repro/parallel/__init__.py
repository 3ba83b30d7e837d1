from repro.parallel.act import (activation_sharding, constrain,
                                shard_residual)
from repro.parallel.sharding import ShardingRules, replicated

__all__ = ["activation_sharding", "constrain", "shard_residual",
           "ShardingRules", "replicated"]
