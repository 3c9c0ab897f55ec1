"""Attention kernel: the pairs the masks and the segment ids show over the
pairs of the tiles the forwards walk, the program's counters
``mxnet_attention_visible_pairs_total`` (computed on the device from each
batch's ids) over ``mxnet_attention_walked_pairs_total`` (from the calls'
shapes), both step scalars of the calls under segment ids: 1 minus it is
what skipping tiles from the ids could save at most.  None where the
program has no such counters or they count nothing."""

VISIBLE = "mxnet_attention_visible_pairs_total"
WALKED = "mxnet_attention_walked_pairs_total"


def read(ctx):
    from chipbench.layer_metrics import _scopes

    visible, walked = _scopes.sample(VISIBLE), _scopes.sample(WALKED)
    if not visible or not walked or not walked["value"]:
        return None
    return visible["value"] / walked["value"]
