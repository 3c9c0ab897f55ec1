"""Expert layer: the fullest held expert's pairs over the mean of the held
experts, the mean of the program's histogram
``mxnet_moe_expert_load_max_over_mean`` (one observation a layer a step,
from device scalars that leave the step beside the loss): the imbalance the
experts' time is to be read beside."""


def read(ctx):
    from chipbench.layer_metrics import _moe, _scopes

    load = _scopes.sample(_moe.LOAD)
    return load["sum"] / load["count"] if load and load["count"] else None
