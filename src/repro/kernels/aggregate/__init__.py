from .ops import exact_for, sum_product
from .ref import sum_product_ref

__all__ = ["exact_for", "sum_product", "sum_product_ref"]
