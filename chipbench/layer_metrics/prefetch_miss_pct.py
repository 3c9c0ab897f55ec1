"""Input staging (``PrefetchIterator.__next__``): the share of batches
asked for while the queue was empty, ``mxnet_prefetch_misses_total`` over
hits and misses, from the process's start (the first batch of a run is
always a miss)."""


def read(ctx):
    from chipbench.layer_metrics import _scopes

    hits = _scopes.sample("mxnet_prefetch_hits_total")
    misses = _scopes.sample("mxnet_prefetch_misses_total")
    if not hits or not misses or not hits["value"] + misses["value"]:
        return None
    return misses["value"] / (hits["value"] + misses["value"]) * 100.0
