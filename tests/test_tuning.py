"""Search-based autotuning tier (mxnet_tpu/tuning/ — ISSUE 16): the
declarative knob registry, the resolve funnel's precedence (trial >
env pin > tuned DB winner > default), the persistent TuningDB's
compile-cache robustness discipline (corrupt / truncated / version
mismatch = silent miss), cross-process search-order determinism, and
the with-tuning-off bit-identity guarantee (the DB is never even
consulted)."""
import json
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx  # noqa: F401  (env/apply_env side effects)
from mxnet_tpu import telemetry, tuning
from mxnet_tpu.tuning import db as tuning_db
from mxnet_tpu.tuning import search as tuning_search

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_KNOB_ENV = ("MXNET_TUNE", "MXNET_TUNE_DB_DIR",
             "MXNET_ALLREDUCE_BUCKET_MB", "MXNET_GRAPH_FUSE_CAP",
             "MXNET_PREFETCH_BUFFER")


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for var in _KNOB_ENV:
        monkeypatch.delenv(var, raising=False)
    telemetry.reset()
    tuning.reset()
    yield
    telemetry.reset()
    tuning.reset()


def _counter(name):
    """Total over all label combinations (the trials counter is
    per-knob labeled)."""
    fam = telemetry.snapshot()["metrics"].get(name) or {}
    return sum(int(s["value"]) for s in fam.get("samples", ()))


# --------------------------------------------------------------------------
# knob registry
# --------------------------------------------------------------------------
def test_registry_population_and_lookup():
    names = tuning.knob_names()
    assert sorted(names) == sorted((
        "allreduce_bucket_mb", "graph_fuse_cap", "prefetch_buffer",
        "serving_batch_buckets", "serving_prefill_buckets",
        "serving_page_size"))
    k = tuning.get_knob("allreduce_bucket_mb")
    assert k.env_var == "MXNET_ALLREDUCE_BUCKET_MB"
    assert k.default == 32 and 0 in k.grid and 64 in k.grid
    with pytest.raises(KeyError):
        tuning.get_knob("no_such_knob")


def test_knob_parse_bad_value_degrades_to_default():
    k = tuning.get_knob("allreduce_bucket_mb")
    assert k.parse(None) == 32
    assert k.parse("8") == 8
    assert k.parse("not-an-int") == 32       # never a crash
    assert k.validate(64) and not k.validate(7)


# --------------------------------------------------------------------------
# resolve precedence: trial > env pin > tuned winner > default
# --------------------------------------------------------------------------
def test_env_override_beats_db_winner(tmp_path, monkeypatch):
    """ISSUE acceptance: an explicit env pin always wins over a stored
    winner, and is reported as pinned."""
    db = tuning.TuningDB(str(tmp_path))
    k = tuning.get_knob("allreduce_bucket_mb")
    assert db.put_winner(k, 8, signature=None)
    monkeypatch.setenv("MXNET_TUNE", "1")
    monkeypatch.setenv("MXNET_TUNE_DB_DIR", str(tmp_path))
    tuning.reset()
    assert tuning.resolve_info("allreduce_bucket_mb") == (8, "tuned")
    monkeypatch.setenv("MXNET_ALLREDUCE_BUCKET_MB", "64")
    tuning.reset()
    assert tuning.resolve_info("allreduce_bucket_mb") == (64, "env")
    # a live trial outranks even the pin (that is what a search IS)
    with tuning.trial_override("allreduce_bucket_mb", 4):
        assert tuning.resolve_info("allreduce_bucket_mb") == \
            (4, "trial")
    assert tuning.resolve_info("allreduce_bucket_mb") == (64, "env")


def test_tuning_off_never_consults_db(tmp_path, monkeypatch):
    """Bit-identity guarantee: with MXNET_TUNE unset the default
    trajectory cannot be steered — a poisoned DB is never even read."""
    db = tuning.TuningDB(str(tmp_path))
    k = tuning.get_knob("allreduce_bucket_mb")
    assert db.put_winner(k, 1, signature=None)
    monkeypatch.setenv("MXNET_TUNE_DB_DIR", str(tmp_path))
    tuning.reset()
    telemetry.reset()
    assert tuning.resolve_info("allreduce_bucket_mb") == \
        (32, "default")
    assert _counter("mxnet_tuning_db_hits_total") == 0
    assert _counter("mxnet_tuning_db_misses_total") == 0


def test_resolve_flows_through_bucket_cap_bytes():
    from mxnet_tpu.parallel import bucketing

    assert bucketing.bucket_cap_bytes() == 32 << 20
    with tuning.trial_override("allreduce_bucket_mb", 8):
        assert bucketing.bucket_cap_bytes() == 8 << 20
    assert bucketing.bucket_cap_bytes() == 32 << 20


def test_effective_config_reports_value_and_source(monkeypatch):
    monkeypatch.setenv("MXNET_GRAPH_FUSE_CAP", "8")
    tuning.reset()
    cfg = tuning.effective_config()
    assert cfg["graph_fuse_cap"] == {"value": 8, "source": "env"}
    assert cfg["allreduce_bucket_mb"] == {"value": 32,
                                          "source": "default"}


# --------------------------------------------------------------------------
# TuningDB robustness: every bad entry is a silent miss, never a crash
# --------------------------------------------------------------------------
def _entry_path(db, key):
    return os.path.join(db.directory, f"{key}.tune")


def test_db_roundtrip_and_winner_validation(tmp_path):
    db = tuning.TuningDB(str(tmp_path))
    k = tuning.get_knob("graph_fuse_cap")
    assert db.put_winner(k, 8, signature=("chain", 24), score=0.5,
                         default_score=0.7, trials=9, unit="s")
    assert db.get_winner(k, signature=("chain", 24)) == 8
    # global fallback: a resolve site without signature context still
    # replays (put_winner published the global copy too)
    assert db.get_winner(k) == 8
    assert db.stats()["entries"] == 2


def test_db_corrupt_truncated_version_mismatch_silent_miss(tmp_path):
    db = tuning.TuningDB(str(tmp_path))
    k = tuning.get_knob("graph_fuse_cap")
    key = db.key(k.name)
    assert db.put_winner(k, 8, publish_global=False)
    assert db.get(key) is not None
    path = _entry_path(db, key)
    base = _counter("mxnet_tuning_db_misses_total")

    # flipped payload byte -> checksum mismatch -> miss
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[:-3] + b"zzz")
    assert db.get(key) is None
    # truncated mid-payload -> size mismatch -> miss
    open(path, "wb").write(blob[:len(blob) - 4])
    assert db.get(key) is None
    # torn header -> miss
    open(path, "wb").write(b'{"sha256": ')
    assert db.get(key) is None
    # empty file -> miss
    open(path, "wb").write(b"")
    assert db.get(key) is None
    assert _counter("mxnet_tuning_db_misses_total") == base + 4

    # format-version bump: the old entry's fingerprint no longer
    # matches -> silent miss (an upgraded runtime starts cold)
    open(path, "wb").write(blob)
    assert db.get(key) is not None
    old = tuning_db._FORMAT_VERSION
    try:
        tuning_db._FORMAT_VERSION = old + 1
        assert db.get(db.key(k.name)) is None
    finally:
        tuning_db._FORMAT_VERSION = old


def test_db_winner_outside_current_grid_is_a_miss(tmp_path):
    """A stale winner from an older grid must not steer."""
    db = tuning.TuningDB(str(tmp_path))
    k = tuning.get_knob("graph_fuse_cap")
    db.put(db.key(k.name), {"format": 1, "knob": k.name,
                            "value": "7777"})
    assert db.get_winner(k) is None


def test_db_missing_dir_and_unwritable_store_are_soft(tmp_path):
    db = tuning.TuningDB(str(tmp_path / "nonexistent"))
    k = tuning.get_knob("graph_fuse_cap")
    assert db.get_winner(k) is None          # miss, not crash
    ro = tuning.TuningDB("/proc/definitely-unwritable")
    assert ro.put_winner(k, 8) is False      # False, not crash


# --------------------------------------------------------------------------
# search: deterministic order, halving, env short-circuit
# --------------------------------------------------------------------------
def test_schedule_is_deterministic_cross_process():
    """Two processes tuning the same knob must try the same candidates
    in the same order (concurrent tuners converge on one winner)."""
    local = {n: tuning_search.schedule(tuning.get_knob(n))
             for n in tuning.knob_names()}
    code = ("import json; from mxnet_tpu import tuning; "
            "from mxnet_tpu.tuning import search; "
            "print(json.dumps({n: search.schedule(tuning.get_knob(n)) "
            "for n in tuning.knob_names()}))")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                       capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr
    remote = json.loads(r.stdout.strip().splitlines()[-1])
    assert json.dumps(remote, sort_keys=True) == \
        json.dumps(local, sort_keys=True)
    # default first, then the grid in declared order, deduped
    sched = local["allreduce_bucket_mb"]
    assert sched["candidates"][0] == 32
    assert sched["candidates"] == [32, 0, 1, 4, 8, 16, 64, 128]
    assert all(n >= 1 for _, n in sched["rungs"])


def test_successive_halving_finds_winner_and_persists(tmp_path):
    db = tuning.TuningDB(str(tmp_path))
    k = tuning.get_knob("graph_fuse_cap")
    cost = {0: 9.0, 4: 5.0, 8: 2.0, 16: 6.0, 32: 7.0, 64: 8.0}
    calls = []

    def measure(value, budget):
        calls.append((value, budget))
        return cost[value]

    report = tuning.tune_knob("graph_fuse_cap", measure, db=db,
                              signature=("fake",), log=lambda m: None)
    assert report["winner"] == 8
    assert report["winner_score"] == 2.0
    assert report["default"] == 16 and report["default_score"] == 6.0
    assert report["delta_pct"] == round(100.0 * (6.0 - 2.0) / 6.0, 2)
    assert report["stored"] is True
    assert report["trials"] == len(calls)
    assert _counter("mxnet_tuning_trials_total") == len(calls)
    # later rungs re-measure at a strictly larger budget
    budgets = sorted({b for _, b in calls})
    assert len(budgets) >= 2 and budgets[-1] > budgets[0]
    assert db.get_winner(k, signature=("fake",)) == 8


def test_warm_process_replays_winner_with_zero_trials(tmp_path,
                                                      monkeypatch):
    db = tuning.TuningDB(str(tmp_path))
    cost = {0: 9.0, 4: 5.0, 8: 2.0, 16: 6.0, 32: 7.0, 64: 8.0}
    tuning.tune_knob("graph_fuse_cap", lambda v, b: cost[v], db=db,
                     log=lambda m: None)
    monkeypatch.setenv("MXNET_TUNE", "1")
    monkeypatch.setenv("MXNET_TUNE_DB_DIR", str(tmp_path))
    tuning.reset()
    telemetry.reset()
    assert tuning.resolve_info("graph_fuse_cap") == (8, "tuned")
    assert _counter("mxnet_tuning_trials_total") == 0
    assert _counter("mxnet_tuning_db_hits_total") == 1
    # the per-process winner memo: a second resolve is a dict probe,
    # not a second disk read
    assert tuning.resolve_info("graph_fuse_cap") == (8, "tuned")
    assert _counter("mxnet_tuning_db_hits_total") == 1
    # chosen-value gauge reports what steered
    samples = telemetry.snapshot()["metrics"][
        "mxnet_tuning_chosen_value"]["samples"]
    by_knob = {s["labels"].get("knob"): s["value"] for s in samples}
    assert by_knob["graph_fuse_cap"] == 8.0


def test_env_pin_short_circuits_search(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_GRAPH_FUSE_CAP", "8")
    tuning.reset()
    report = tuning.tune_knob("graph_fuse_cap",
                              lambda v, b: 1.0 / 0.0,  # must not run
                              db=tuning.TuningDB(str(tmp_path)),
                              log=lambda m: None)
    assert report["source"] == "env" and report["trials"] == 0
    assert report["pinned"] == 8


def test_failing_trial_scores_inf_and_is_pruned(tmp_path):
    def measure(value, budget):
        if value == 0:
            raise RuntimeError("candidate exploded")
        return float(value)

    report = tuning.tune_knob("graph_fuse_cap", measure,
                              db=tuning.TuningDB(str(tmp_path)),
                              log=lambda m: None)
    assert report["winner"] == 4            # smallest surviving score
    assert all(f["value"] != 0 for f in report["final_rung"])


def test_trial_override_restores_on_exception():
    try:
        with tuning.trial_override("graph_fuse_cap", 4):
            assert tuning.resolve("graph_fuse_cap") == 4
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    assert tuning.resolve_info("graph_fuse_cap") == (16, "default")
