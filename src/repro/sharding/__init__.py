from .rules import (ExecConfig, param_specs, cache_specs, batch_specs,
                    opt_state_specs, make_shard_fn, logical_batch_axes)

__all__ = ["ExecConfig", "param_specs", "cache_specs",
           "batch_specs", "opt_state_specs", "make_shard_fn",
           "logical_batch_axes"]
