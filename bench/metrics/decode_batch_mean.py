"""Active rows per decode dispatch in the traced stretch (engine scheduler)."""


def read(record):
    rows = [len(s["decode_ctx"]) for s in record["steps"] if s["decode_ctx"]]
    return sum(rows) / len(rows) if rows else None
