from repro.runtime.sharding import (
    batch_specs,
    cache_specs,
    param_specs,
    state_specs,
)
from repro.runtime.step import make_train_step

__all__ = [
    "param_specs",
    "batch_specs",
    "cache_specs",
    "state_specs",
    "make_train_step",
]
