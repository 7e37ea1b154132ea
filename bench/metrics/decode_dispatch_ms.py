"""Device time of one decode program execution, mean over the traced
stretch (model step, the jitted decode program), averaged over chips."""
from bench.metrics_common import program_ms


def read(record):
    return program_ms(record, "decode")
