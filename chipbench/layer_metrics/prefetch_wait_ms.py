"""Input staging (``PrefetchIterator.__next__``): milliseconds a batch the
consumer waited on the queue, from the program's own histogram
``mxnet_prefetch_wait_seconds`` (exact sum over count), from the process's
start: the set-up steps, the untraced window and the traced one together.
``input_wait_ms`` times the same call from outside."""


def read(ctx):
    from chipbench.layer_metrics import _scopes

    return _scopes.mean_ms("mxnet_prefetch_wait_seconds")
