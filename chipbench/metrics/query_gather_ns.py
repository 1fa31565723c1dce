"""Device time of the query program's bucket-row gathers, per key answered
(ns/key; moves ops_per_s).

The operations of ``jit_cuckoo_query`` in phase ``row_gather``
(``core/layout.gather_by_row``'s ``[rows, 128]`` gather) among the traced
window's top operations, over the keys answered in the window; phases come
from the compiled text of the query program (``yardstick/scopes.py``).
"""

from yardstick.scopes import query_ns_per_key


def read(run: dict):
    return query_ns_per_key(run, ("row_gather",))
