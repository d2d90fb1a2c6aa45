from .bitmask import pack_validity, unpack_validity, bitmask_bitwise_or
from .lru import LruDict
from .tracing import span

__all__ = ["pack_validity", "unpack_validity", "bitmask_bitwise_or",
           "LruDict", "span"]
