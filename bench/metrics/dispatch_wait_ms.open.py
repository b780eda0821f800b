"""Mean time the dispatcher held a batch open for more requests (first
request to batch closed), from its ``dispatcher.batch_wait`` spans."""


def read(run):
    if run.spans is None:
        return None
    recs = run.span_records("dispatcher.batch_wait", run.t0 * 1e9,
                            run.t_end * 1e9)
    if not len(recs):
        return None
    return float((recs["t1"] - recs["t0"]).mean()) / 1e6
