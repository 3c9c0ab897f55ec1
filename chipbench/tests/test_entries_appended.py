"""``BENCHMARK.json``'s per-layer entries as later PRs may leave them: the
32 that PR 34 left stand first, in their order, each with its keys, and
their ``workloads`` lists have grown at the end only; whatever was appended
since has its reader's file beside the others and lists cells that exist.
(``test_part_metrics.py`` pins the count at 32 and the last six names, so it
is red for every PR that appends a metric: PERF.md section 7.)"""
from __future__ import annotations

import json
import os
import subprocess

import toy

PR34 = 32      # per-layer entries when test_part_metrics.py was written
KEYS = {"name", "unit", "better", "source", "layer", "moves"}


def _bench():
    with open(os.path.join(toy.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_accepted_entries_stand_first_and_what_follows_is_whole():
    from test_part_metrics import NEW

    bench = _bench()
    cells = [w["name"] for w in bench["workloads"]]
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    assert len(names) == len(set(names)) >= PR34
    assert names[PR34 - len(NEW):PR34] == list(NEW)
    layers = {m["layer"] for m in entries[:PR34]}
    for entry in entries:
        assert KEYS <= set(entry) <= KEYS | {"workloads"}, entry["name"]
        assert os.path.isfile(os.path.join(
            toy.REPO, "chipbench", "layer_metrics", entry["name"] + ".py"))
        listed = entry.get("workloads", cells)
        assert listed and set(listed) <= set(cells), entry["name"]
        # cells are appended in the order the benchmark gained them
        assert listed == [c for c in cells if c in listed], entry["name"]
        assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
    for entry in entries[PR34:]:
        assert "workloads" in entry, entry["name"]
        if entry["name"].endswith("_roofline"):
            assert (entry["unit"], entry["better"]) == ("%", "higher")
    # a layer's name is one of PERF.md's list: a new one is there too
    with open(os.path.join(toy.REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in entries} - layers:
        assert layer in perf, layer


def test_no_accepted_entry_lost_a_cell_against_the_parent_commit():
    """Against ``HEAD``'s ``BENCHMARK.json`` where git has one: every entry
    there is here at its place with its keys unchanged, and a ``workloads``
    list has only grown at its end."""
    got = subprocess.run(["git", "-C", toy.REPO, "show",
                          "HEAD:BENCHMARK.json"], capture_output=True,
                         text=True)
    if got.returncode:
        return
    then, now = json.loads(got.stdout), _bench()
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(then[key], now[key]):
            plain = {k: v for k, v in old.items() if k != "workloads"}
            assert plain == {k: v for k, v in new.items()
                             if k != "workloads"}, (key, old["name"])
            if "workloads" in old:
                assert new["workloads"][:len(old["workloads"])] \
                    == old["workloads"], (key, old["name"])
        assert len(now[key]) >= len(then[key])
