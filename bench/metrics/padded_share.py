"""Serving: zero rows the batcher padded in, over all rows the forwards
ran, in the window (%).  Moves ``images_per_s``."""


def read(r):
    w = r["window"]
    rows = w["rows_executed"] + w["rows_padded"]
    return 100 * w["rows_padded"] / rows if rows else None
