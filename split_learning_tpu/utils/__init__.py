from split_learning_tpu.utils.backend import (
    configure_compile_cache, reexec_pinned_cpu)
from split_learning_tpu.utils.config import Config

__all__ = ["Config", "configure_compile_cache", "reexec_pinned_cpu"]
