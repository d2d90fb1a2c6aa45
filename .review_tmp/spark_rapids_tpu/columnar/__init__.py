from .column import Column, make_string_column, strings_from_padded
from .table import Table

__all__ = ["Column", "Table", "make_string_column", "strings_from_padded"]
