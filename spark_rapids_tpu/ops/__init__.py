from .hash import murmur_hash3_32, xxhash64, DEFAULT_XXHASH64_SEED
from .cast_string import (CastError, string_to_integer, string_to_float,
                          string_to_integer_with_base,
                          integer_to_string_with_base)
from .cast_decimal import string_to_decimal
from .decimal_utils import (add_decimal128, sub_decimal128,
                            multiply_decimal128, divide_decimal128,
                            remainder_decimal128)
from .cast_decimal_to_string import decimal_to_non_ansi_string
from .zorder import interleave_bits, hilbert_index
from .datetime_rebase import (rebase_gregorian_to_julian,
                              rebase_julian_to_gregorian)
from .bloom_filter import (BloomFilter, bloom_filter_create, bloom_filter_put,
                           bloom_filter_merge, bloom_filter_probe,
                           bloom_filter_serialize, bloom_filter_deserialize)
from .timezones import (TimeZoneDB, from_timestamp_to_utc_timestamp,
                        from_utc_timestamp_to_timestamp,
                        is_supported_time_zone)
from .cast_float_to_string import float_to_string
from .format_float import format_float
from .row_conversion import (convert_from_rows_fixed_width_optimized,
                             convert_to_rows,
                             convert_to_rows_fixed_width_optimized,
                             convert_from_rows, row_layout)
from .parse_uri import (parse_uri_to_protocol, parse_uri_to_host,
                        parse_uri_to_query, parse_uri_to_query_literal,
                        parse_uri_to_query_column)
from .histogram import create_histogram_if_valid, percentile_from_histogram
from .map_utils import from_json
from .gather import (take, take_live, take_table, apply_boolean_mask,
                     outer_join_columns)
from .sort import sort_table_capped, sorted_order, sort_table
from .aggregate import groupby_aggregate, groupby_aggregate_capped
from .window import window_functions
from .join import (full_join, full_join_counted, full_join_parts, inner_join,
                   inner_join_carrying, inner_join_capped,
                   inner_join_capped_tail, left_join, left_join_capped,
                   left_join_counted, left_semi_join, left_anti_join,
                   outer_join_parts, semi_join_mask)
from .copying import (concat_columns, concat_tables, slice_table,
                      split_table, halve_table, replace_nulls, if_else,
                      drop_duplicates)

# ---- admission at the op boundary ------------------------------------------
# Every public Table-level op crosses the memory arbiter when a DeviceSession
# is active (runtime/admission.py) — the TPU-native analogue of every RMM
# allocation crossing spark_resource_adaptor::do_allocate
# (SparkResourceAdaptorJni.cpp:1733). Factors are working-set multipliers
# over input buffer bytes (outputs + transient fusion scratch); reservations
# shrink to true output bytes post-dispatch. Internal cross-module calls
# import the submodules directly, so admission happens exactly once per
# public-op call.
from ..runtime.admission import admitted_op as _admitted_op

_ADMITTED_FACTORS = {
    "murmur_hash3_32": 1.5, "xxhash64": 1.5,
    "string_to_integer": 2.0, "string_to_float": 2.0,
    "string_to_integer_with_base": 2.0, "integer_to_string_with_base": 3.0,
    "string_to_decimal": 2.0,
    "add_decimal128": 2.0, "sub_decimal128": 2.0, "multiply_decimal128": 3.0,
    "divide_decimal128": 3.0, "remainder_decimal128": 3.0,
    "decimal_to_non_ansi_string": 3.0,
    "interleave_bits": 2.0, "hilbert_index": 2.0,
    "rebase_gregorian_to_julian": 2.0, "rebase_julian_to_gregorian": 2.0,
    "from_timestamp_to_utc_timestamp": 2.0, "from_utc_timestamp_to_timestamp": 2.0,
    "float_to_string": 4.0, "format_float": 4.0,
    "convert_to_rows": 3.0, "convert_to_rows_fixed_width_optimized": 3.0,
    "convert_from_rows": 3.0, "convert_from_rows_fixed_width_optimized": 3.0,
    "parse_uri_to_protocol": 2.0, "parse_uri_to_host": 2.0,
    "parse_uri_to_query": 2.0, "parse_uri_to_query_literal": 2.0,
    "parse_uri_to_query_column": 2.0,
    "create_histogram_if_valid": 2.0, "percentile_from_histogram": 2.0,
    "from_json": 3.0,
    "take": 2.0, "take_live": 2.0, "take_table": 2.0,
    "apply_boolean_mask": 2.0, "outer_join_columns": 2.0,
    "sorted_order": 2.0, "sort_table": 3.0, "sort_table_capped": 3.0,
    "groupby_aggregate": 2.0, "groupby_aggregate_capped": 2.0,
    "window_functions": 3.0,
    "inner_join": 3.0, "inner_join_carrying": 3.0,
    "inner_join_capped": 3.0,
    "inner_join_capped_tail": 3.0, "left_join": 3.0,
    "left_join_counted": 3.0, "left_join_capped": 3.0, "full_join": 3.0,
    "full_join_counted": 3.0, "full_join_parts": 3.0,
    "outer_join_parts": 3.0,
    "left_semi_join": 2.0, "left_anti_join": 2.0, "semi_join_mask": 2.0,
    # slice/split/halve are deliberately NOT admitted: they run inside the
    # SplitAndRetry recovery path when memory is already short, and their
    # pieces replace the parent batch (net-zero new working set) — the
    # reference likewise splits batches that rollback made spillable
    # (RmmSpark.java:461-490).
    "concat_columns": 2.0, "concat_tables": 2.0, "replace_nulls": 2.0,
    "if_else": 2.0, "drop_duplicates": 2.0,
    "bloom_filter_put": 2.0, "bloom_filter_merge": 2.0,
    "bloom_filter_probe": 2.0,
}
for _name, _factor in _ADMITTED_FACTORS.items():
    globals()[_name] = _admitted_op(globals()[_name], factor=_factor)
del _name, _factor

__all__ = [
    "murmur_hash3_32", "xxhash64", "DEFAULT_XXHASH64_SEED",
    "CastError", "string_to_integer", "string_to_float",
    "string_to_integer_with_base", "integer_to_string_with_base",
    "string_to_decimal", "add_decimal128", "sub_decimal128",
    "multiply_decimal128", "divide_decimal128", "remainder_decimal128",
    "decimal_to_non_ansi_string", "interleave_bits", "hilbert_index",
    "rebase_gregorian_to_julian", "rebase_julian_to_gregorian",
    "BloomFilter", "bloom_filter_create", "bloom_filter_put",
    "bloom_filter_merge", "bloom_filter_probe", "bloom_filter_serialize",
    "bloom_filter_deserialize",
    "TimeZoneDB", "from_timestamp_to_utc_timestamp",
    "from_utc_timestamp_to_timestamp", "is_supported_time_zone",
    "float_to_string", "format_float",
    "convert_to_rows", "convert_to_rows_fixed_width_optimized",
    "convert_from_rows", "convert_from_rows_fixed_width_optimized",
    "row_layout",
    "parse_uri_to_protocol", "parse_uri_to_host", "parse_uri_to_query",
    "parse_uri_to_query_literal", "parse_uri_to_query_column",
    "create_histogram_if_valid", "percentile_from_histogram",
    "from_json",
    "take", "take_live", "take_table", "apply_boolean_mask",
    "outer_join_columns", "sorted_order",
    "sort_table",
    "sort_table_capped",
    "groupby_aggregate", "groupby_aggregate_capped", "window_functions",
    "inner_join", "inner_join_carrying", "inner_join_capped",
    "inner_join_capped_tail",
    "left_join", "left_join_counted", "left_join_capped",
    "full_join", "full_join_counted", "full_join_parts",
    "outer_join_parts", "left_semi_join",
    "left_anti_join", "semi_join_mask",
    "concat_columns", "concat_tables", "slice_table", "split_table",
    "halve_table", "replace_nulls", "if_else", "drop_duplicates",
]
