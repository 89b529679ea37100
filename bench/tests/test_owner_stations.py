"""The five readers of a served job's stations (added in PR 49): the
manifest's entries found BY NAME (their `workloads` compared with `>=`, never
pinned), the readers on hand-made jobs whose answers are worked out here, on
a rehearsed window of each served cell, and on runs that have nothing to
read — the parent's artifacts, a job no owner ran — where each gives None and
never raises."""

import os
import time

import pytest

import lib

SERVE = "serve (daemon, queue, owner)"
NEW = {"owner_wait_s": ("s/job", "program_span", SERVE),
       "owner_envelope_s": ("s/job", "program_span", SERVE),
       "publish_s": ("s/job", "program_span", SERVE),
       "owner_gap_s": ("s/job", "program_span", SERVE),
       "ckpt_mb_per_job": ("MB/job", "program_counter", "engines")}
CELLS = ["ci-stream-4p8", "ci-cohort-4p"]
BM = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))


def _read(name, run):
    return lib.load_module(os.path.join(lib.BENCH, "layers", name + ".py"),
                           "bench_layer_" + name).read(run)


def _run(jobs, warmup=()):
    return {"out": {"artifacts": {"jobs": list(jobs),
                                  "warmup": list(warmup)},
                    "trace_dir": None},
            "trace": None, "mix": {}, "pins": {}}


def _job(jid, kind, st, wall=None, ckpt=None, mates=()):
    """A job as the `stream` driver summarises it, with the stations `st`
    (seconds after an arbitrary zero)."""
    sv = {"stations": dict(st), "batched_with": list(mates)}
    if wall is not None:
        sv["job_wall_s"] = wall
    return {"id": jid, "kind": kind, "label": "4p8", "status": "done",
            "runner": "ci-a", "commit": 1, "client_s": 9.0, "serve": sv,
            "submitted_at": st.get("submitted_at"),
            "started_at": st.get("claimed_at"),
            "finished_at": st.get("finished_at"), "phases": {},
            "counters": {} if ckpt is None else {"checkpoint.bytes": ckpt},
            "gauges": {}, "dispatches": {}, "result": {}, "env": {}}


def _stations(claimed, sent, began, ended, received, finished):
    return {"submitted_at": claimed - 0.5, "enqueued_at": claimed - 0.499,
            "claimed_at": claimed, "owner_sent_at": sent,
            "owner_began_at": began, "owner_ended_at": ended,
            "owner_received_at": received, "finished_at": finished}


def test_the_entries_in_the_manifest_by_name():
    by_name = {m["name"]: m for m in BM["per_layer"]}
    layers = {m["layer"] for m in BM["per_layer"]}
    for name, (unit, source, layer) in NEW.items():
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"] >= CELLS, name   # a later served cell may join
        assert (m["unit"], m["source"], m["layer"]) == (unit, source, layer)
        assert (m["moves"], m["better"]) == ("states_per_s", "lower")
        assert lib.NAME_RE.match(name) and lib.UNIT_RE.match(unit)
        assert layer in layers
        assert os.path.isfile(os.path.join(lib.BENCH, "layers",
                                           name + ".py")), name
    # this PR only adds: what it reads beside is as it was
    for name in ("serve_path_s", "queue_wait_s"):
        assert by_name[name]["workloads"] >= CELLS, name
    for cell in CELLS:
        names = {m["name"] for m in lib.resolve(cell)["per_layer"]}
        assert names >= set(NEW), cell


def test_the_readers_on_jobs_worked_out_by_hand():
    """Two workers, one owner: job a runs 10.0-12.0, job b (claimed at 10.1)
    stands on the lock until a's answer is back at 12.05 and runs
    12.07-13.07, the replay c 13.2-13.21."""
    a = _job("a", "edit", _stations(10.0, 10.01, 10.02, 12.0, 12.05, 12.06),
             wall=1.98, ckpt=3_000_000)
    b = _job("b", "edit", _stations(10.1, 12.06, 12.07, 13.07, 13.1, 13.12),
             wall=1.0, ckpt=1_000_000)
    c = _job("c", "rerun", _stations(13.0, 13.19, 13.2, 13.21, 13.22, 13.225),
             wall=0.01)
    run = _run([a, b, c])
    # all three jobs: (0.01 + 1.96 + 0.19) / 3
    assert _read("owner_wait_s", run) == pytest.approx(2.16 / 3)
    # the searched jobs' requests: (2.04 - 1.98 + 1.04 - 1.0) / 2
    assert _read("owner_envelope_s", run) == pytest.approx(0.05)
    assert _read("publish_s", run) == pytest.approx((0.01 + 0.02 + 0.005) / 3)
    # 12.07 - 12.0 and 13.2 - 13.07
    assert _read("owner_gap_s", run) == pytest.approx(0.1)
    assert _read("ckpt_mb_per_job", run) == pytest.approx(2.0)
    # a set-up job that ran BETWEEN two window jobs is an envelope too: the
    # gap after it counts, a gap OVER it would not be one
    w = _job("w", "edit", _stations(11.0, 13.11, 13.12, 13.15, 13.16, 13.17),
             wall=0.03)
    run = _run([a, b, c], warmup=[w])
    assert _read("owner_gap_s", run) == pytest.approx(
        ((12.07 - 12.0) + (13.2 - 13.15)) / 2)


def test_a_cohort_counts_once_for_the_envelope_and_the_gap():
    """Four members of one vbatch carry their leader's owner stations and
    are published one after the other."""
    st = _stations(20.0, 20.01, 20.02, 30.0, 30.06, 30.07)
    members = [_job(f"m{i}", "edit", dict(st, finished_at=30.07 + 0.01 * i),
                    wall=9.98, ckpt=500_000,
                    mates=[f"m{k}" for k in range(4) if k != i])
               for i in range(4)]
    st2 = _stations(20.5, 30.08, 30.1, 40.0, 40.05, 40.06)
    more = [_job(f"n{i}", "edit", dict(st2, finished_at=40.06 + 0.01 * i),
                 wall=9.9, ckpt=500_000) for i in range(4)]
    run = _run(members + more)
    assert _read("owner_envelope_s", run) == pytest.approx(
        ((30.06 - 20.01 - 9.98) + (40.05 - 30.08 - 9.9)) / 2)
    assert _read("owner_gap_s", run) == pytest.approx(30.1 - 30.0)
    assert _read("owner_wait_s", run) == pytest.approx(
        (0.01 + (30.08 - 20.5)) / 2)
    assert _read("publish_s", run) == pytest.approx(0.01 + 0.015)
    assert _read("ckpt_mb_per_job", run) == pytest.approx(0.5)


def test_the_readers_where_there_is_nothing_to_read():
    """The parent's artifacts (no stations, no counter), a job that no owner
    ran (the daemon's own stations alone), another driver's run: None."""
    bare = _job("p", "edit", {})
    del bare["serve"]["stations"]
    own = _job("q", "edit", {"submitted_at": 1.0, "enqueued_at": 1.001,
                             "claimed_at": 1.1, "finished_at": 2.0})
    for run in (_run([bare]), _run([own]), _run([]),
                {"out": {"artifacts": {}}, "trace": None},
                {"out": {}, "trace": None}):
        for name in NEW:
            assert _read(name, run) is None, name
    # one envelope alone has no gap; a job without `job_wall_s` no envelope
    lone = _job("r", "edit", _stations(1.0, 1.1, 1.2, 2.0, 2.1, 2.2))
    run = _run([lone])
    assert _read("owner_gap_s", run) is None
    assert _read("owner_envelope_s", run) is None
    assert _read("owner_wait_s", run) == pytest.approx(0.1)


@pytest.mark.parametrize("cell,driver", [("ci-stream-4p8", "stream"),
                                         ("ci-cohort-4p", "cohort")])
def test_the_readers_on_a_rehearsed_window(cell, driver):
    """One rehearsed window of each served cell (XLA:CPU, toy size): every
    reader reads a number, the stations stand in order in every job, and
    the owner's wait is what `queue_wait_s` does not hold."""
    mod = lib.load_module(os.path.join(lib.BENCH, "drivers", driver + ".py"),
                          "bench_driver_" + driver)
    out = mod.run(dict(lib.resolve(cell), seed=2147483791, seconds=0.0,
                       trace=False, rehearsal=True, t0=time.time()))
    assert out["correct"] is True
    run = {"out": out, "trace": None, "mix": lib.resolve(cell)["mix"],
           "pins": lib.resolve(cell)["pins"], "bench_dir": lib.BENCH}
    got = {name: _read(name, run) for name in NEW}
    for name, value in got.items():
        assert value is not None and value >= 0, (name, value)
    assert got["ckpt_mb_per_job"] > 0
    order = ("submitted_at", "enqueued_at", "claimed_at", "owner_sent_at",
             "owner_began_at", "owner_ended_at", "owner_received_at",
             "finished_at")
    for j in out["artifacts"]["jobs"] + out["artifacts"]["warmup"]:
        st = j["serve"]["stations"]
        marks = [st[k] for k in order]
        assert marks == sorted(marks), j["id"]
        assert st["claimed_at"] == j["started_at"]
        assert st["finished_at"] == j["finished_at"]
    # a job's serve path is its queue wait, its owner wait, the envelope and
    # the publish, and what is left is the client's (HTTP, lint, polling)
    for j in out["artifacts"]["jobs"]:
        sv, st = j["serve"], j["serve"]["stations"]
        held = (st["claimed_at"] - st["submitted_at"]) + \
            sv["owner_wait_s"] + sv["owner_envelope_s"] + sv["publish_s"]
        assert 0 <= j["client_s"] - sv["job_wall_s"] - held < 1.0, j["id"]
