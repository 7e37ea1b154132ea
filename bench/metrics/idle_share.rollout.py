"""Share of the traced window in which no operation ran on the device, in
%, averaged over the chips used (device)."""
from bench.metrics_common import idle_share


def read(record):
    return idle_share(record)
