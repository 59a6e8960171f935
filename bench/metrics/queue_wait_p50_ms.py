"""Scheduler queue under open-loop traffic: median milliseconds from a
request's submission to the start of the first work item that carries it
(``Ticket.dispatched_s``), over every request of the window; one never
dispatched (refused at the door, say) counts as infinitely long."""
import math

from bench.harness.measure import nearest_rank


def read(ctx):
    recs = ctx.win.records
    if not ctx.open_loop or not recs or not hasattr(recs[0].ticket, "dispatched_s"):
        return None
    waits = [math.inf if r.ticket.dispatched_s is None
             else r.ticket.dispatched_s - r.ticket.submitted_s for r in recs]
    return 1e3 * nearest_rank(waits, 0.50)
