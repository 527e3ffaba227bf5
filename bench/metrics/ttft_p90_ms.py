"""90th percentile, by nearest rank, of the time to first token of every
request due in the window, in ms: from the request's due time to its
first streamed token.  A request that never got one counts as infinitely
late."""

import math

from bench import measure


def read(run):
    due = measure.due_in_window(run)
    if not due:
        return None
    waits = [math.inf if r["first"] is None else r["first"] - r["due"]
             for r in due]
    return 1e3 * measure.pct(waits, 90)
