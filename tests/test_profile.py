r"""Learned capacity profiles (ISSUE 6, compile/cache.py): a completed
resident run persists its capacity buckets next to the compile cache;
the next engine on the same (module, layout) starts there, so its one
warm-up compile covers the whole run and the timed window records ZERO
recompiles.  Stale/foreign profiles degrade to the overflow-growth path
with a named reason — never a wrong-capacity crash.
"""

import json
import os

import pytest

from conftest import REFERENCE  # noqa: F401

from jaxmc.front.cfg import parse_cfg
from jaxmc.sem.modules import Loader, bind_model
from jaxmc import obs

SPECS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "specs")


def load_model():
    return bind_model(
        Loader([SPECS]).load_path(os.path.join(SPECS, "constoy.tla")),
        parse_cfg(open(os.path.join(SPECS, "constoy.cfg")).read()))


@pytest.fixture()
def store(tmp_path, monkeypatch):
    d = str(tmp_path / "profiles")
    monkeypatch.setenv("JAXMC_PROFILE_STORE", d)
    monkeypatch.delenv("JAXMC_CAP_PROFILE", raising=False)
    return d


def _run_resident(tel=None, **kw):
    from jaxmc.backend.bfs import TpuExplorer
    with obs.use(tel or obs.NullTelemetry()):
        ex = TpuExplorer(load_model(), store_trace=False, resident=True,
                         **kw)
        r = ex.run()
    return ex, r


def test_profile_saved_and_drives_zero_window_recompiles(store):
    # run 1: no profile — overflow-growth trains the caps, completion
    # persists them
    tel1 = obs.Telemetry()
    ex1, r1 = _run_resident(tel1)
    assert r1.ok
    assert tel1.gauges.get("profile.status") == "saved"
    files = os.listdir(store)
    assert len(files) == 1 and files[0].endswith(".json")

    # run 2: a FRESH engine (new process in the bench flow) loads the
    # profile; after its one warm-up run, a timed re-run must report
    # zero fresh compiles — the window_recompiles == 0 contract
    tel2 = obs.Telemetry()
    from jaxmc.backend.bfs import TpuExplorer
    with obs.use(tel2):
        ex2 = TpuExplorer(load_model(), store_trace=False, resident=True)
        assert tel2.gauges.get("profile.status") == "loaded"
        assert ex2._res_caps_hint, "profile caps must hint the engine"
        rw = ex2.run()              # warm-up (the one compile)
        tel2.reset_levels("timed")
        rt = ex2.run()              # timed window
    assert rw.ok and rt.ok
    assert (rt.generated, rt.distinct) == (r1.generated, r1.distinct)
    window_recompiles = sum(1 for lv in tel2.levels
                            if lv.get("fresh_compile"))
    assert window_recompiles == 0, \
        f"profile failed to prevent in-window recompiles: {tel2.levels}"


def test_stale_profile_degrades_with_named_reason(store):
    tel1 = obs.Telemetry()
    _ex, r = _run_resident(tel1)
    assert r.ok
    path = os.path.join(store, os.listdir(store)[0])
    p = json.load(open(path))
    p["layout_sig"] = "0" * 16
    json.dump(p, open(path, "w"))
    tel2 = obs.Telemetry()
    ex2, r2 = _run_resident(tel2)
    assert r2.ok, "a stale profile must never fail the run"
    # the degrade is counted; the final status gauge reads "saved"
    # because the completed run re-persisted a fresh profile
    assert tel2.counters.get("profile.degrades", 0) >= 1
    assert (r2.generated, r2.distinct) == (r.generated, r.distinct)


def test_foreign_schema_and_garbage_degrade(store):
    tel1 = obs.Telemetry()
    _ex, r = _run_resident(tel1)
    path = os.path.join(store, os.listdir(store)[0])
    # foreign schema
    p = json.load(open(path))
    p["schema"] = "somebody.else/9"
    json.dump(p, open(path, "w"))
    from jaxmc.compile.cache import load_capacity_profile
    # single-chip resident profiles live under the backend-platform
    # namespace since ISSUE 11 (variant "cpu" on this box): the load
    # must name the same variant the engine saved
    variant = p.get("variant", "")
    tel = obs.Telemetry()
    assert load_capacity_profile("constoy", p["layout_sig"],
                                 tel=tel, variant=variant) is None
    assert str(tel.gauges.get("profile.status")).startswith(
        "degraded:foreign schema")
    _ex2, r2 = _run_resident(obs.Telemetry())
    assert r2.ok, "a foreign profile must never fail the run"
    # unreadable garbage
    with open(path, "w") as fh:
        fh.write("{not json")
    tel = obs.Telemetry()
    assert load_capacity_profile("constoy", p["layout_sig"],
                                 tel=tel, variant=variant) is None
    assert str(tel.gauges.get("profile.status")).startswith(
        "degraded:unreadable")


def test_profile_opt_out(store, monkeypatch):
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")
    tel = obs.Telemetry()
    _ex, r = _run_resident(tel)
    assert r.ok
    assert not os.path.isdir(store) or not os.listdir(store)


def test_malformed_caps_degrade(store):
    from jaxmc.compile.cache import load_capacity_profile, \
        profile_path, _PROFILE_SCHEMA
    os.makedirs(store, exist_ok=True)
    path = profile_path("constoy", "x" * 16)
    json.dump({"schema": _PROFILE_SCHEMA, "module": "constoy",
               "layout_sig": "x" * 16,
               "caps": {"SC": -5, "FCap": 1, "AccCap": 1, "VC": 1}},
              open(path, "w"))
    tel = obs.Telemetry()
    with obs.use(tel):
        assert load_capacity_profile("constoy", "x" * 16, tel=tel) is None
    assert str(tel.gauges.get("profile.status")).startswith(
        "degraded:malformed caps")
