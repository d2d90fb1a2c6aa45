from .parquet_footer import (ParquetFooter, StructElement, ListElement,
                             MapElement, ValueElement, ColumnChunkStats,
                             RowGroupStats, read_footer_stats)
from .parquet import (ParquetChunkedReader, ParquetSource, read_parquet,
                      select_row_groups)

# IO admission: a parquet read has no resident input buffers, so the
# working-set estimate comes from the source size (encoded bytes × a
# decompression/decode expansion factor) — the same pre-dispatch-estimate
# contract as the op boundary (runtime/admission.py).
from ..runtime.admission import admitted_op as _admitted_op


def _parquet_read_estimate(source, *args, **kwargs) -> int:
    import os
    if isinstance(source, (bytes, bytearray, memoryview)):
        return 3 * len(source)
    try:
        return 3 * os.path.getsize(source)
    except (OSError, TypeError):
        return 0


read_parquet = _admitted_op(read_parquet, estimator=_parquet_read_estimate)

__all__ = ["ParquetFooter", "StructElement", "ListElement", "MapElement",
           "ValueElement", "ParquetChunkedReader", "ParquetSource",
           "read_parquet", "read_footer_stats", "select_row_groups",
           "ColumnChunkStats", "RowGroupStats"]
