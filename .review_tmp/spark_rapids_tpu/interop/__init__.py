from .arrow import to_arrow, from_arrow, export_to_c, import_from_c

__all__ = ["to_arrow", "from_arrow", "export_to_c", "import_from_c"]
