from .bitmask import pack_validity, unpack_validity, bitmask_bitwise_or
from .lru import LruDict
from .tracing import func_range, range_ctx, start_trace, stop_trace, trace

__all__ = ["pack_validity", "unpack_validity", "bitmask_bitwise_or",
           "LruDict",
           "func_range", "range_ctx", "start_trace", "stop_trace", "trace"]
