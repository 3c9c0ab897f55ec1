"""The readers of the program's step records, on hand-made events and
records: the two clocks' join and its own check, a chip's idle time in
three, and the longest interval between steps seen complete.

    python -m pytest chipbench/tests -q
"""
from __future__ import annotations

import json
import os

import pytest

import toy

CELL = "bert_base.seq512.fused"
NEW = ("step_interval_longest_over_median", "idle_under_program_span_pct",
       "idle_with_steps_queued_pct")
CLOCKS_APART = 1000.0     # perf_counter reads this much more than the trace
MAIN, PRODUCER = 11, 22   # thread ids


@pytest.fixture(scope="module")
def cell():
    from chipbench.harness.cell import Cell

    return Cell(CELL, root=toy.REPO)


def _record(step, prepare, execute, stage=None, seen=None, **more):
    """A record as the program writes it, its times given on the trace's
    clock."""
    at = lambda stamps, *rest: [t + CLOCKS_APART for t in stamps] + list(rest)
    spans = {"train_step.prepare": at(prepare), "train_step.execute":
             at(execute)}
    if stage:
        spans["prefetch.stage"] = at(stage, PRODUCER)
    since = {"seconds": 0.2, "nivcsw": 0, "nvcsw": 3, "majflt": 0,
             "inblock": 0, "oublock": 0, "cpu_s": 0.004, "compiles": 0,
             "cache_misses": 0, "cache_retrieval_s": 0.0,
             "backend_compile_s": 0.0, "gc2": []}
    return dict({"kind": "fused", "net": "Toy", "track": 1, "step": step,
                 "batch": step, "thread": MAIN, "in_flight": min(step, 2),
                 "opened": prepare[0] + CLOCKS_APART - 1e-5, "spans": spans,
                 "since_previous_call": since, "unready_at": None,
                 "seen_complete": seen and seen + CLOCKS_APART,
                 "interval_s": None}, **more)


def _ops(shift=0.0):
    """Three steps' runs on a chip: ``copy.1`` opens each, once a step;
    ``fusion.3`` runs twice a step inside ``fusion.2``'s time."""
    out = []
    for start, long in ((0.25, 0.30), (0.70, 0.25), (1.20, 0.35)):
        out += [["%copy.1 = f32[8] copy(...)", start + shift, 0.05],
                ["%fusion.2 = f32[8] fusion(...)", start + shift + 0.05,
                 long],
                ["%fusion.3 = f32[8] fusion(...)", start + shift + 0.06,
                 0.01],
                ["%fusion.3 = f32[8] fusion(...)", start + shift + 0.08,
                 0.01]]
    return out


HOST = [["next_batch", 0.0, 0.01], ["dispatch_step", 0.10, 0.10],
        ["dispatch_step", 0.30, 0.10], ["dispatch_step", 0.50, 0.10],
        ["wait_loss", 0.60, 1.40]]
# the chip is idle over 0-0.25, 0.60-0.70, 1.00-1.20 and 1.60-2.00


def _records():
    return [_record(4, (0.10, 0.15), (0.15, 0.19)),
            # its batch was staged while the chip sat between two runs
            _record(5, (0.31, 0.35), (0.35, 0.39), stage=(0.62, 0.68)),
            _record(6, (0.505, 0.55), (0.55, 0.59))]


def _ctx(records, devices, monkeypatch, stalls=(), table=None):
    """The readers' context over made events, the program's snapshot and
    its registry of op tables replaced by the test's (``table``: the op
    names of the fused step, None for a program that has no table)."""
    from chipbench.harness import trace
    from mxnet_tpu import profiler, telemetry

    tables = type(profiler._OP_SCOPES)()
    if table is not None:
        tables["train_step:Toy"] = {name: {"scope": "mx_forward", "classes":
                                           ["forward"]} for name in table}
    monkeypatch.setattr(profiler, "_OP_SCOPES", tables)
    events = {"devices": devices, "host": HOST}
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "step_records": {"records": records, "stalls": list(stalls)}})
    return {"trace": events, "window": trace.window_of(events),
            "steps": 3, "dispatched": 0,
            "summary": trace.summary(events, 3)}


def test_a_chips_idle_time_goes_to_one_of_three(cell, monkeypatch, capsys):
    ctx = _ctx(_records(), {"0": {"ops": _ops()}}, monkeypatch)
    under = cell.read_layer_metric("idle_under_program_span_pct", ctx)
    queued = cell.read_layer_metric("idle_with_steps_queued_pct", ctx)
    # of the window's 2 s: under a span, 0.10-0.19 of the first gap (the
    # call of step 4) and 0.62-0.68 of the second (the producer's stage);
    # with a step queued and nothing open, 0.19-0.25 (step 4 handed over,
    # not begun), the rest of the second gap (steps 5 and 6 both handed
    # over) and all of the third (step 6); nothing queued and nothing open,
    # 0-0.10 and the drain's 1.60-2.00
    assert under == pytest.approx((0.09 + 0.06) / 2.0 * 100)
    assert queued == pytest.approx((0.06 + 0.04 + 0.20) / 2.0 * 100)
    idle = cell.read_layer_metric("device_idle_pct", ctx)
    assert idle == pytest.approx(0.95 / 2.0 * 100)
    said = capsys.readouterr().out
    assert "join: 3 records (steps 4-6) inside their dispatch_step" in said
    assert "prefetch.stage 0.060000" in said
    assert f"nothing open: {(0.10 + 0.40) / 2.0 * 100:.4f}% " in said
    assert "200.000 ms on chip 0 before step 6" in said
    assert said.count("steps: join:") == 1          # joined once a run


def test_the_three_shares_sum_to_the_idle_share_over_chips(cell,
                                                           monkeypatch,
                                                           capsys):
    """Two chips whose runs begin at different times: each share is the
    chips' mean, and with what is left they make ``device_idle_pct``."""
    ctx = _ctx(_records(), {"0": {"ops": _ops()},
                            "1": {"ops": _ops(shift=0.013)}}, monkeypatch)
    under = cell.read_layer_metric("idle_under_program_span_pct", ctx)
    queued = cell.read_layer_metric("idle_with_steps_queued_pct", ctx)
    said = capsys.readouterr().out
    rest = float(said.split("nothing open: ")[1].split("%")[0])
    assert 0 < under < queued
    assert under + queued + rest == pytest.approx(
        cell.read_layer_metric("device_idle_pct", ctx), abs=1e-4)


def test_a_steps_run_begins_at_an_instruction_of_the_steps_own(
        cell, monkeypatch):
    """A call runs two small programs for its key before it hands the step
    over: their ops run once a step too, earlier, and are not in the
    program's table of the fused step."""
    ops = _ops()
    for call in (0.12, 0.32, 0.52):       # inside each call's prepare
        ops.append(["%convert_element_type.9 = u32[] convert(...)", call,
                    0.001])
    plain = _ctx(_records(), {"0": {"ops": _ops()}}, monkeypatch)
    expected = cell.read_layer_metric("idle_with_steps_queued_pct", plain)
    ctx = _ctx(_records(), {"0": {"ops": ops}}, monkeypatch,
               table=("copy.1", "fusion.2", "fusion.3"))
    assert cell.read_layer_metric("idle_with_steps_queued_pct", ctx) \
        == pytest.approx(expected)
    # without the table the key's op would pass for the run's first, and no
    # step would ever have been queued
    ctx = _ctx(_records(), {"0": {"ops": ops}}, monkeypatch)
    assert cell.read_layer_metric("idle_with_steps_queued_pct", ctx) == 0.0


def test_a_collection_is_a_span_of_the_program(cell, monkeypatch, capsys):
    records = _records()
    records[2]["since_previous_call"]["gc2"] = [
        [CLOCKS_APART + 0.45, CLOCKS_APART + 0.47],     # the chip was busy
        [CLOCKS_APART + 1.70, CLOCKS_APART + 1.75]]
    ctx = _ctx(records, {"0": {"ops": _ops()}}, monkeypatch)
    assert cell.read_layer_metric("idle_under_program_span_pct", ctx) \
        == pytest.approx((0.09 + 0.06 + 0.05) / 2.0 * 100)
    assert "collection 0.050000" in capsys.readouterr().out


@pytest.mark.parametrize("fault", ["shifted", "fewer", "no_once_a_step"])
def test_records_that_do_not_fit_give_none_and_say_why(cell, monkeypatch,
                                                       capsys, fault):
    records, ops = _records(), _ops()
    if fault == "shifted":
        # a call that ends after its dispatch_step span does
        records[1] = _record(5, (0.31, 0.35), (0.35, 0.43))
    elif fault == "fewer":
        del records[0]
    else:
        ops = ops[:-4]      # the third run never shows on the chip
    ctx = _ctx(records, {"0": {"ops": ops}}, monkeypatch)
    for name in NEW[1:]:
        assert cell.read_layer_metric(name, ctx) is None
    said = capsys.readouterr().out
    assert {"shifted": "no join: step 5's call, shifted by 1000.000000 s",
            "fewer": "no join: 3 dispatch_step spans in the trace, 2 records",
            "no_once_a_step": "no split: no instruction ran 3 times on chip "
                              "0"}[fault] in said


def test_a_program_without_records_gives_none_and_does_not_raise(
        cell, monkeypatch):
    from mxnet_tpu import telemetry

    ctx = _ctx([], {"0": {"ops": _ops()}}, monkeypatch)
    monkeypatch.setattr(telemetry, "snapshot", lambda: {"steps": []})
    assert [cell.read_layer_metric(n, ctx) for n in NEW] == [None] * 3


def test_the_longest_interval_over_the_median(cell, monkeypatch, capsys):
    """Two windows of calls: a step is seen complete by the call three
    later; the windows' last steps are seen after the window (by the next
    one's first call, by the reader) and count in neither."""
    def window(first, t0, stall_at=None):
        out, t = [], t0
        for i in range(12):
            t += 0.050 + (1.450 if i == stall_at else 0.0)
            out.append(_record(first + i, (t, t + 0.002),
                               (t + 0.002, t + 0.004)))
        for i, r in enumerate(out):
            later = out[i + 3]["opened"] if i + 3 < len(out) else 99.0 \
                + CLOCKS_APART
            r["seen_complete"] = later
        return out

    setup = [_record(0, (1.0, 1.1), (7.1, 7.2), seen=7.5)]
    setup[0]["spans"]["train_step.compile"] = [CLOCKS_APART + 1.1,
                                               CLOCKS_APART + 7.1]
    untraced, traced = window(1, 10.0, stall_at=7), window(13, 30.0)
    # the call that saw the stalled step complete says what the process did
    untraced[7]["since_previous_call"].update(nivcsw=41, majflt=7)
    untraced[7]["since_previous_call"]["gc2"] = [[CLOCKS_APART + 10.5,
                                                  CLOCKS_APART + 10.75]]
    ctx = _ctx(setup + untraced + traced, {"0": {"ops": _ops()}},
               monkeypatch, stalls=[{"step": 5, "interval_s": 1.5,
                                     "median_s": 0.05, "records": []}])
    ctx.update(steps=12, dispatched=12)
    got = cell.read_layer_metric(NEW[0], ctx)
    assert got == pytest.approx(1.5 / 0.05)
    said = capsys.readouterr().out
    # 9 steps a window are seen inside it: 8 intervals each
    assert "16 intervals, median 50.000 ms; the longest 1500.000 ms ended " \
        "at step 5 (batch 5, 2 in flight at its dispatch), seen by 1 " \
        "call(s) of steps [8]" in said
    assert "nivcsw 41, nvcsw 3, majflt 7" in said
    assert "collector 0.250000 s" in said
    assert "stalls the program kept: 1; step 5: 1.500 s" in said
    # a sound run reads 1
    ctx = _ctx(setup + window(1, 10.0) + traced, {"0": {"ops": _ops()}},
               monkeypatch)
    ctx.update(steps=12, dispatched=12)
    assert cell.read_layer_metric(NEW[0], ctx) == pytest.approx(1.0)


def test_the_three_entries_are_the_last_and_list_every_cell():
    with open(os.path.join(toy.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    last = bench["per_layer"][-3:]
    assert tuple(m["name"] for m in last) == NEW
    for m, layer, moves in zip(last, ("fused step", "fused step", "device"),
                               ("step_ms_p95", "samples_per_s_per_chip",
                                "samples_per_s_per_chip")):
        assert (m["layer"], m["moves"], m["better"]) == (layer, moves,
                                                         "lower")
        assert m["workloads"] == cells


def test_a_window_of_a_hundred_thousand_gaps_is_read_in_seconds(
        cell, monkeypatch):
    """A traced window's ops lie a microsecond or so apart: 80 steps of
    1,500 ops make 120,000 gaps a chip, and the split walks them once (the
    first chip run of these readers did not end: every gap was walked for
    every gap)."""
    import time

    from chipbench.harness import trace
    from mxnet_tpu import telemetry

    steps, per_step, op_s, gap_s, step_s = 80, 1500, 30e-6, 1e-6, 0.05
    ops, host, records = [], [], []
    for k in range(steps):
        t = 0.01 + k * step_s
        host += [["next_batch", t - 0.009, 0.0004],
                 ["dispatch_step", t - 0.008, 0.004]]
        records.append(_record(k, (t - 0.008, t - 0.006), (t - 0.006,
                                                           t - 0.0045),
                               stage=(t - 0.0085, t - 0.0075)))
        for i in range(per_step):
            ops.append([f"%fusion.{i} = f32[8] fusion(...)",
                        t + i * (op_s + gap_s), op_s])
    host.append(["wait_loss", steps * step_s, 0.01])
    events = {"devices": {"0": {"ops": ops}, "1": {"ops": ops}},
              "host": host}
    from mxnet_tpu import profiler

    monkeypatch.setattr(profiler, "_OP_SCOPES", type(profiler._OP_SCOPES)())
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "step_records": {"records": records, "stalls": []}})
    ctx = {"trace": events, "window": trace.window_of(events),
           "steps": steps, "dispatched": 0,
           "summary": trace.summary(events, steps)}
    t0 = time.perf_counter()
    under = cell.read_layer_metric("idle_under_program_span_pct", ctx)
    queued = cell.read_layer_metric("idle_with_steps_queued_pct", ctx)
    assert time.perf_counter() - t0 < 20
    idle = cell.read_layer_metric("device_idle_pct", ctx)
    # every gap between two ops of a run is under nothing and behind
    # nothing queued; the rest is what the spans and the hand-over take
    assert 0 < under < idle and 0 <= queued < idle
