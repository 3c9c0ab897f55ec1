"""Input staging (``gluon/data/prefetcher.py::PrefetchIterator``):
milliseconds a step the loop waited to take the next batch, from the
benchmark's own ``next_batch`` span."""


def read(ctx):
    if not ctx["dispatched"] or "next_batch" not in ctx["spans"]:
        return None
    return ctx["spans"]["next_batch"] / ctx["dispatched"] * 1e3
