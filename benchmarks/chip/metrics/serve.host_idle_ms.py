"""Device idle time per served batch in the traced window while the host
serves: the idle gaps that the trace reduction names by the program's
``landmarks.estimate`` span (seed columns built and sent), ``serve.topk``
(the ranked ids and scores brought back), ``landmarks.answer`` (the
fallback decision) or ``serve`` (cache lookups, the batch brought back for
the cache's put), summed over the window, over its batches (serve front
layer).  A gap is named by the innermost span open at its midpoint."""

SPANS = ("landmarks.estimate", "serve.topk", "landmarks.answer", "serve")


def read(rec: dict):
    if rec["op"] != "ppr_serve" or not rec["trace"] or not rec["items"]:
        return None
    gaps = [s for name, s in rec["trace"]["idle_gaps"] if name in SPANS]
    return 1e3 * sum(gaps) / len(rec["items"])
