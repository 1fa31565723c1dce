"""Device idle share of the traced bulk window (percent; moves ops_per_s)."""

from yardstick.shares import idle_share


def read(run: dict):
    return idle_share(run)
