"""What the part readers share: the traced window's device time by part of
the model, through the ``part`` the program's op-to-scope table gives every
instruction (``mxnet_tpu.profiler.scopes_of``).

The program resolves an instruction to one part by one rule: what it is by
itself (an attention kernel, a grouped product or an all-reduce by its
instruction name, else the innermost part scope in its own ``op_name``:
``mx_attn_proj``, ``mx_ffn``, ``mx_norm``, ``mx_rope``, ``mx_embed``,
``mx_head``, ``mx_loss``, the expert layer's three, the attention
backward's and the plain forward's); else, for a fusion, the one part among
what it fused, ``mx_optimizer`` not counting, so that a weight gradient's
matmul with Adam's update as epilogue is its matmul's part; else ``mixed``
(several), ``optimizer`` (``mx_optimizer`` alone) or ``""`` (nothing names
it: the compiler's copies and fills).  Forward, backward and recomputation
count alike in a part.  Every instant goes to the innermost event covering
it (``_scopes.self_seconds``), so the parts sum to ``step_device_ms``.

A table whose rows carry no part (a program before the parts, or a cached
executable of one) gives every reader here None.
"""
from __future__ import annotations

UNNAMED = ("", "mixed")     # what ``step_unnamed_device_pct`` holds


def split(ctx):
    """``{"busy": ms, "parts": {part: ms}, "known": parts of the table}``,
    milliseconds a step a chip of the traced window, or None with no
    table, no step, or a table without parts.  Read once a run and kept in
    ``ctx``; prints the whole split and what no part names."""
    if "_part_split" not in ctx:
        ctx["_part_split"] = _split(ctx)
    return ctx["_part_split"]


def _split(ctx):
    from chipbench.harness import trace
    from chipbench.layer_metrics import _scopes

    table = _scopes.step_table()
    if not table or not ctx["steps"]:
        return None
    known = {row.get("part", "") for row in table.values()}
    if known <= {""}:
        return None
    busy, parts, loose = 0.0, {}, {}
    for dev in ctx["trace"]["devices"].values():
        events = trace.clip(dev["ops"], *ctx["window"])
        busy += trace.length(trace.union(events))
        for name, seconds in _scopes.self_seconds(events).items():
            row = table.get(name)
            part = row.get("part", "") if row else ""
            parts[part] = parts.get(part, 0.0) + seconds
            if part in UNNAMED:
                key = (name, part or "no part",
                       row["scope"] if row else "not in the table")
                loose[key] = loose.get(key, 0.0) + seconds
    per = 1e3 / len(ctx["trace"]["devices"]) / ctx["steps"]
    parts = {part: seconds * per for part, seconds in parts.items()}
    busy *= per
    say = lambda s: print("chipbench: parts: " + s, flush=True)
    say(", ".join(f"{part or 'no part'} {ms:.3f}" for part, ms in
                  sorted(parts.items(), key=lambda kv: -kv[1])))
    total = sum(parts.values())
    say(f"they sum to {total:.3f} ms a step of busy {busy:.3f} "
        f"(apart by {abs(total - busy):.4f}); mixed "
        f"{parts.get('mixed', 0.0) / busy * 100:.2f}% and no part "
        f"{parts.get('', 0.0) / busy * 100:.2f}% of busy")
    for what in ("no part", "mixed"):
        largest = sorted(((seconds, name, scope) for (name, part, scope),
                          seconds in loose.items() if part == what),
                         reverse=True)[:5]
        say(f"largest of {what}: " + "; ".join(
            f"{name} {seconds * per:.3f} [{scope}]"
            for seconds, name, scope in largest))
    return {"busy": busy, "parts": parts, "known": known}


def part_ms(ctx, *names):
    """Milliseconds a step a chip in ops of the parts ``names`` together,
    or None where there is no split or the step's table holds no op of any
    of them (a net without that part)."""
    got = split(ctx)
    if not got or not got["known"] & set(names):
        return None
    return sum(got["parts"].get(name, 0.0) for name in names)
