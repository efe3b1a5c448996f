from .ops import exact_for, pack_column, sum_product, sum_product_packed
from .ref import sum_product_ref

__all__ = ["exact_for", "pack_column", "sum_product", "sum_product_packed",
           "sum_product_ref"]
