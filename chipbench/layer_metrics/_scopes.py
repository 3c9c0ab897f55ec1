"""What the scope readers share: the join of a traced window's device
events with the program's op-to-scope table, and the program's counters.

The program names the parts of its fused step from inside
(``jax.named_scope``: ``mx_forward``, ``mx_optimizer``,
``mxnet_flash_attention_bwd``) and keeps, for each executable it compiles,
a table from HLO instruction name to scope and classes
(``mxnet_tpu.profiler.op_scopes()``).  The trace shows device events by
instruction name only, so the table is the join.  A program that has no
such table (an older one), or an empty one, gives every reader here None.
"""
from __future__ import annotations

CLASSES = ("forward", "backward", "optimizer")
ATTENTION_BWD = "mxnet_flash_attention_bwd"


def step_table():
    """The newest ``train_step:*`` table of the program, or None where it
    has none or one in which no op has a class (an executable that a build
    without the scopes compiled, found in a compile cache shared with it)."""
    from mxnet_tpu import profiler

    tables = getattr(profiler, "op_scopes", dict)()
    found = [t for name, t in tables.items() if name.startswith("train_step:")]
    if found and any(row["classes"] for row in found[-1].values()):
        return found[-1]
    return None


def self_seconds(events):
    """Seconds by op name, every instant of busy time given to the
    innermost event that covers it (the one that began last): a ``while``
    op and the ops of its body both lie on the line, and a sum over events
    would count that time twice.  The values sum to the union's length."""
    from chipbench.harness.trace import op_name

    out = {}
    open_ = []   # [name, end] of the events begun and not yet over
    t = 0.0

    def run_to(to):
        nonlocal t
        while open_ and t < to:
            name, end = open_[-1]
            if end <= t:
                open_.pop()
                continue
            upto = min(end, to)
            out[name] = out.get(name, 0.0) + upto - t
            t = upto
        t = to

    for name, start, dur in sorted(events, key=lambda e: e[1]):
        run_to(start)
        open_.append([op_name(name), start + dur])
    run_to(float("inf"))
    return out


def own_class(scope):
    """The class of one ``op_name``, by the program's rule."""
    if "mx_optimizer" in scope:
        return "optimizer"
    if "mx_forward" in scope:
        return "backward" if "transpose(" in scope else "forward"
    return None


def split(ctx):
    """Milliseconds a step a chip of the traced window by part of the step:
    ``forward`` / ``backward`` / ``optimizer`` (ops whose only class it is),
    ``attention_bwd`` (ops whose scope holds the backward's name), ``busy``
    (the union, as ``step_device_ms`` has it).  None with no table or no
    step.  Read once a run and kept in ``ctx``; prints what bounds it."""
    if "_scope_split" not in ctx:
        ctx["_scope_split"] = _split(ctx)
    return ctx["_scope_split"]


def _split(ctx):
    from chipbench.harness import trace

    table = step_table()
    if not table or not ctx["steps"]:
        return None
    out = dict.fromkeys(CLASSES + ("attention_bwd", "busy"), 0.0)
    rest = {}      # (op, what it is) -> seconds, of time outside the three
    by_own = dict.fromkeys(CLASSES, 0.0)
    for dev in ctx["trace"]["devices"].values():
        events = trace.clip(dev["ops"], *ctx["window"])
        out["busy"] += trace.length(trace.union(events))
        for name, seconds in self_seconds(events).items():
            row = table.get(name)
            classes = row["classes"] if row else []
            if row and ATTENTION_BWD in row["scope"]:
                out["attention_bwd"] += seconds
            own = own_class(row["scope"]) if row else None
            if own:
                by_own[own] += seconds
            if len(classes) == 1:
                out[classes[0]] += seconds
            else:
                what = "+".join(classes) if classes else \
                    "unscoped" if row else "not in the table"
                rest[name, what] = rest.get((name, what), 0.0) + seconds
    per = 1e3 / len(ctx["trace"]["devices"]) / ctx["steps"]
    out = {k: v * per for k, v in out.items()}
    kinds = {}
    for (_, what), seconds in rest.items():
        kinds[what] = kinds.get(what, 0.0) + seconds * per
    say = lambda s: print("chipbench: scopes: " + s, flush=True)
    say(f"table of {len(table)} ops; busy {out['busy']:.3f} ms a step, "
        + ", ".join(f"{c} only {out[c]:.3f}" for c in CLASSES))
    say("outside the three: " + ", ".join(
        f"{k} {v:.3f}" for k, v in sorted(kinds.items(), key=lambda kv:
                                          -kv[1])))
    say("largest of them: " + ", ".join(
        f"{name} [{what}] {seconds * per:.3f}" for (name, what), seconds in
        sorted(rest.items(), key=lambda kv: -kv[1])[:5]))
    say("with every fusion given to its own op's class instead: " + ", ".join(
        f"{c} {by_own[c] * per:.3f}" for c in CLASSES))
    return out


def sample(name, **labels):
    """The program's sample of metric ``name`` with ``labels``
    (``mxnet_tpu.telemetry.snapshot()``), or None."""
    from mxnet_tpu import telemetry

    family = telemetry.snapshot()["metrics"].get(name)
    for s in family["samples"] if family else ():
        if s["labels"] == labels:
            return s
    return None


def mean_ms(name, **labels):
    """Milliseconds an observation of a histogram of seconds (its exact
    sum over its count), or None where it has none."""
    s = sample(name, **labels)
    return s["sum"] / s["count"] * 1e3 if s and s["count"] else None
