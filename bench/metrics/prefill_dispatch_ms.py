"""Device time of one prefill program execution, mean over the traced
stretch (model step, the jitted prefill program), averaged over chips."""
from bench.metrics_common import program_ms


def read(record):
    return program_ms(record, "prefill")
