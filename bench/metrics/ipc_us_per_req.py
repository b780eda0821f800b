"""Microseconds per completed request spent in the fabric's wire and
reactor: channel sends (requests and replies), reactor drains and reply
fills, summed over every process, from the program's own spans.  This is
wall time on the request's path, ring-slot waits included, not CPU time.
Nothing is read where a span ring wrapped and lost records."""

KINDS = ("channel.send", "reactor.drain", "reactor.reply_fill")


def read(run):
    if run.spans is None or run.spans.total_drops:
        return None
    lo, hi = run.t0 * 1e9, run.t_end * 1e9
    ns = 0.0
    for kind in KINDS:
        recs = run.span_records(kind, lo, hi)
        ns += float((recs["t1"] - recs["t0"]).sum())
    n = run.completed_in_window()
    return ns / 1e3 / n if n and ns else None
