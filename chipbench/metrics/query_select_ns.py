"""Device time of the query program's lane selects and tag matches, per key
answered (ns/key; moves ops_per_s).

The operations of ``jit_cuckoo_query`` in phases ``lane_select`` (the
masked lane reductions of ``core/layout.gather_by_row``) and ``tag_match``
(unpack, compare, any) among the traced window's top operations, over the
keys answered in the window (``yardstick/scopes.py``).
"""

from yardstick.scopes import query_ns_per_key


def read(run: dict):
    return query_ns_per_key(run, ("lane_select", "tag_match"))
