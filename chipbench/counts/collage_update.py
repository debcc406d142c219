"""Bytes the ``collage_update`` kernel must move: one pass over a bucket.

Every state field of the strategy (θ, and δθ, m, v-hi, v-lo or the master
copy as it has them) is read and written once, and the gradient read once;
its few metric partials are left out. For strategy C with bf16 fields that
is 5 × 2 × 2 + 2 = 22 bytes per element. No floating-point operation count
is kept: the update is bound by memory."""
from __future__ import annotations


def bytes_moved(elements: int, field_itemsizes: list, grad_itemsize: int
                ) -> int:
    return elements * (2 * sum(field_itemsizes) + grad_itemsize)
