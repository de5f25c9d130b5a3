"""What the end-to-end reading holds that a median of searches would hide:
(window - searches x median search wall) over the window.  A stall in one
search, or time lost between searches, reads here."""

import statistics


def read(ctx):
    walls = ctx["search_walls"]
    window = ctx["window_s"]
    return 100.0 * (window - len(walls) * statistics.median(walls)) / window
