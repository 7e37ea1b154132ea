"""Pool pages holding context over pages committed by admission (each
resident request counted at its ``max_total``), averaged over the traced
stretch's dispatches, in % (engine scheduler / KV allocator)."""


def read(record):
    xs = [s["pages_used"] / s["pages_committed"] for s in record["steps"]
          if s["pages_committed"]]
    return 100.0 * sum(xs) / len(xs) if xs else None
