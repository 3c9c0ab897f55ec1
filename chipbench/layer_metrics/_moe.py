"""What the expert layer's readers share: device time under one of the
program's scopes (``mx_moe_route``, ``mx_moe_experts``), forward and backward
alike, and the routed pairs a step from the program's counters.  A program
without the scope or the counter (an older one) gives every reader None."""
from __future__ import annotations

PAIRS = "mxnet_moe_routed_pairs_total"
LOAD = "mxnet_moe_expert_load_max_over_mean"


# The grouped products (``jax.lax.ragged_dot``) as the op-to-scope table has
# them: under the program's scope where the compiler keeps it
# (``.../mx_moe_experts/ragged_dot``), else under XLA's own op_name: on the
# TPU the product expands to custom calls ``ragged-dot-*`` (the product and
# the tile metadata it walks) that carry no scope of the program's
# (``chipbench/testdata/moe_step.*``, recorded on the chip).
GROUPED_XLA = "ragged-dot"
GROUPED_JAX = "ragged_dot"


def scope_ms(ctx, scope, grouped=False):
    """Milliseconds a step a chip of the traced window in ops whose own
    scope holds ``scope`` (``transpose(jvp(...))`` of it too, and what a
    checkpoint computes again), every instant given to the innermost op
    that covers it.  With ``grouped`` the grouped products count too,
    found through the table as the comment above says.  None with no
    op-to-scope table, no step, or no op of that scope in the table; and,
    with ``grouped``, None where the table or the trace shows no grouped
    product at all (a compiler that names them otherwise): no number,
    rather than one that leaves the products out."""
    from chipbench.harness import trace
    from chipbench.layer_metrics import _scopes

    table = _scopes.step_table()
    if not table or not ctx["steps"]:
        return None
    ops = {name for name, row in table.items() if scope in row["scope"]}
    if not ops:
        return None
    products = set()
    if grouped:
        products = {name for name, row in table.items()
                    if row["scope"].startswith(GROUPED_XLA)
                    or (name in ops and GROUPED_JAX in row["scope"])}
        if not products:
            return None
        ops |= products
    seconds, seen = 0.0, not grouped
    for dev in ctx["trace"]["devices"].values():
        events = trace.clip(dev["ops"], *ctx["window"])
        for name, s in _scopes.self_seconds(events).items():
            if name in ops:
                seconds += s
                seen = seen or name in products
    if not seen:
        return None
    return seconds * 1e3 / len(ctx["trace"]["devices"]) / ctx["steps"]


def pairs_per_step(ctx):
    """(token, expert) pairs the expert layers computed a step, all layers
    together: the program's counter over the steps it covers, which the
    histogram counts (one observation a layer a step).  None without
    them."""
    from chipbench.layer_metrics import _scopes

    pairs, load = _scopes.sample(PAIRS), _scopes.sample(LOAD)
    layers = ctx["cfg"].get("num_hidden_layers")
    if not pairs or not load or not load["count"] or not layers:
        return None
    return pairs["value"] / (load["count"] / layers)
