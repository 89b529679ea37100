"""The reduction from a trace to busy / idle / per-op seconds / named gaps."""

import os

import pytest

import reduce as R

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_merge_and_complement():
    m = R.merge([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)])
    assert m == [[1, 4], [5, 8]]
    assert R.total(m) == 6
    assert R.complement(m, 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert R.clip(m, 2, 6) == [(2, 4), (5, 6)]


def test_self_times_take_children_out_of_the_parent():
    ev = [("while", 0, 10), ("a", 1, 4), ("b", 5, 9), ("c", 12, 13)]
    assert R.self_times(ev) == {"while": 3, "a": 3, "b": 4, "c": 1}


def test_op_name_strips_numbering():
    assert R.op_name("fusion.123") == "fusion"
    assert R.op_name("copy-done.4.1") == "copy-done"
    assert R.op_name("while") == "while"
    assert R.op_name(
        "%sort.8 = (s32[65536]{0:T(1024)S(1)}, s32[65536]{0:T(1024)}) "
        "sort(s32[65536]{0:T(1024)S(1)} %multiply_add_fusion.2, "
        "s32[65536]{0:T(1024)S(1)} %iota.2), dimensions={0}") == \
        "sort (s32[65536],s32[65536])"
    assert R.op_name("%fusion.123 = s32[4096,2]{1,0:T(8,128)} fusion("
                     "s32[4096,2]{1,0} %p), kind=kLoop") == \
        "fusion s32[4096,2]"
    assert R.op_name("PjitFunction(search)") == "PjitFunction(search)"


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    from jax.profiler import ProfileData
    txt = open(os.path.join(DATA, "synthetic_trace.txt")).read()
    txt = "\n".join(ln for ln in txt.splitlines()
                    if not ln.startswith("#"))
    path = tmp_path_factory.mktemp("tr") / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(txt))
    return R.reduce_trace(str(path))


def test_busy_union_and_idle_share(synthetic):
    assert synthetic["window_s"] == pytest.approx(10e-6)
    assert synthetic["busy_s"] == pytest.approx(4.5e-6)
    assert synthetic["search_busy_s"] == pytest.approx(4.0e-6)
    assert synthetic["searches_traced"] == 2
    assert synthetic["devices_traced"] == 1
    idle = 1 - synthetic["busy_s"] / synthetic["window_s"]
    assert idle == pytest.approx(0.55)


def test_per_op_seconds_are_self_times(synthetic):
    ops = dict(synthetic["device_ops"])
    assert ops["sort"] == pytest.approx(1.5e-6)
    assert ops["fusion"] == pytest.approx(1.0e-6 + 0.5e-6)
    assert ops["copy"] == pytest.approx(1.0e-6)
    assert ops["while"] == pytest.approx(0.5e-6)   # 3000 - 1000 - 1500
    assert "jit_run(1)" not in ops                  # only the XLA Ops line


def test_gaps_are_named_by_span_and_host_event(synthetic, monkeypatch):
    gaps = dict(synthetic["idle_gaps"])
    # all gaps are under 50 us here, so they fall under short-gaps
    assert sum(gaps.values()) == pytest.approx(5.5e-6)
    monkeypatch.setattr(R, "NAMED_GAP_NS", 100)
    spans = [("bench.window", 1000, 11000), ("bench.search", 1500, 5500),
             ("bench.search", 6500, 9000)]
    others = [("TransferToDevice", 5100, 6900),
              ("PjitFunction(run)", 8100, 10400)]
    assert R.name_gap((5000, 7000), spans, others) == \
        "bench.window:TransferToDevice"
    assert R.name_gap((8000, 10500), spans, others) == \
        "bench.window:PjitFunction(run)"
    assert R.name_gap((1000, 2000), spans, []) == "bench.search"


def test_recorded_tpu_trace():
    """A real trace from the chip (record_trace.py): three searches of a
    while_loop of sorts with host sleeps between them."""
    path = os.path.join(DATA, "small_tpu.xplane.pb")
    red = R.reduce_trace(path)
    assert red["searches_traced"] == 3 and red["devices_traced"] == 1
    assert 0 < red["search_busy_s"] <= red["busy_s"] < red["window_s"]
    # three 2 ms sleeps sit inside the window, outside the searches
    assert red["window_s"] - red["busy_s"] > 0.006
    ops = dict(red["device_ops"])
    assert any("sort" in k for k in ops), ops
    assert sum(ops.values()) == pytest.approx(red["busy_s"], rel=0.02)
    gaps = dict(red["idle_gaps"])
    assert all(k.startswith("bench.") for k in gaps), gaps
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=0.02)
