from . import autograd, device, dispatch, dtype, flags, rng, tensor  # noqa: F401
from . import compile_cache  # noqa: F401
from . import resilience  # noqa: F401  (registers its memory_stats providers)
from .tensor import Tensor, to_tensor  # noqa: F401

# Persistent XLA compile cache + counters, on for every entry point from the
# first import (FLAGS_xla_compile_cache=0 disables). Idempotent and never
# raises.
compile_cache.initialize()
