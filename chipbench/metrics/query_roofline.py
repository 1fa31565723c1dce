"""Query program's share of the HBM roofline (percent; moves ops_per_s).

76 bytes per key at 16-bit fingerprints in 16-slot buckets: the key, its
answer and both candidate buckets (``yardstick/bytes.py``), over the device
time of the system's programs in the window, which in a bulk query cell are
the query programs alone.
"""

from yardstick.shares import roofline_share


def read(run: dict):
    return roofline_share(run)
