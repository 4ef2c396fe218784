"""The trace reduction: on synthetic planes, and on a recorded fixture.

`fixtures/api-small-250ms.xplane.pb` is the first 250 ms of device events
of a traced `crs-full.api-small` run on the v5e (my chip run, PR 26), cut
by `tests/trim_xplane.py`.  Its expected numbers were reckoned apart from
`harness/xplane.py`, by a sweep over the events' edges.
"""

from pathlib import Path

import pytest

from harness import xplane

FIXTURE = (Path(__file__).resolve().parent.parent / "fixtures"
           / "api-small-250ms.xplane.pb")


def test_union_merges_overlaps_and_touching_intervals():
    assert xplane.union([(5, 20), (0, 10), (30, 40), (40, 45), (31, 32)]) == [
        [0, 20], [30, 45]]
    assert xplane.union([]) == []


def test_names():
    assert xplane.program_name("jit_fold_rows(8159546111474316357)") == (
        "jit_fold_rows")
    assert xplane.op_name(
        "%fusion.18 = s32[4096]{0:T(1024)S(1)} fusion(s32[257] %x)") == (
        "%fusion.18")


def test_reduce_window_on_synthetic_planes():
    planes = {"/device:TPU:0": {
        "XLA Ops": [("%a = x", 0, 10), ("%b = x", 5, 20), ("%c = x", 30, 40),
                    ("%d = x", 100, 110)],
        "XLA Modules": [("jit_scan(1)", 0, 20), ("jit_fold(2)", 30, 40),
                        ("jit_scan(1)", 100, 110)]}}
    got = xplane.reduce_window(planes)
    assert got["busy_s"] == pytest.approx(40e-9)
    assert got["window_s"] == pytest.approx(110e-9)
    assert got["programs"]["jit_scan"] == {
        "seconds": pytest.approx(30e-9), "count": 2}
    assert got["programs"]["jit_fold"]["count"] == 1
    assert got["device_ops"][0] == ["jit_scan:%b", pytest.approx(15e-9)]
    assert got["idle_gaps"] == [["jit_fold -> jit_scan", pytest.approx(60e-9)],
                                ["jit_scan -> jit_fold", pytest.approx(10e-9)]]


def test_two_devices_are_averaged_and_an_idle_one_left_out():
    planes = {
        "/device:TPU:0": {"XLA Ops": [("%a = x", 0, 10)], "XLA Modules": []},
        "/device:TPU:1": {"XLA Ops": [("%a = x", 0, 30)], "XLA Modules": []},
        "/device:TPU:2": {"XLA Ops": [], "XLA Modules": []}}
    got = xplane.reduce_window(planes)
    assert got["device_planes"] == 2
    assert got["busy_s"] == pytest.approx(20e-9)
    assert got["window_s"] == pytest.approx(30e-9)


def test_no_device_event_reads_nothing():
    assert xplane.reduce_window({})["busy_s"] == 0.0


def test_recorded_fixture():
    got = xplane.reduce_window(xplane.load(FIXTURE))
    assert got["device_planes"] == 1
    assert got["busy_s"] == pytest.approx(635221e-9, rel=1e-9)
    assert got["window_s"] == pytest.approx(187208605e-9, rel=1e-9)
    idle_share = 100.0 * (1.0 - got["busy_s"] / got["window_s"])
    assert idle_share == pytest.approx(99.66068813984272)
    p = got["programs"]
    assert {n: v["count"] for n, v in p.items()} == {
        "jit_convert_element_type": 4, "jit_broadcast_in_dim": 4,
        "jit_scan_pairs_jit": 6, "jit_fold_rows": 6,
        "jit_expand_requests": 2}
    assert p["jit_scan_pairs_jit"]["seconds"] == pytest.approx(561440e-9)
    assert p["jit_fold_rows"]["seconds"] == pytest.approx(45162e-9)
    assert p["jit_expand_requests"]["seconds"] == pytest.approx(31827e-9)
    assert got["device_ops"][0][0] == "jit_scan_pairs_jit:%while.1"
    # idle time is all of the window that is not busy
    gaps = sum(s for _n, s in got["idle_gaps"])
    assert gaps <= got["window_s"] - got["busy_s"] + 1e-12


def test_the_roofline_reader_on_the_fixture():
    """scan_hbm_roofline from the fixture's scan time and a hand-made
    traced slice: 1,000 live rows of tier 64 and 100 of tier 256 (the
    whole window's rows, ten times as many, are not what it reads)."""
    import importlib.util

    from harness import scrape

    path = (Path(__file__).resolve().parent.parent / "layer_metrics"
            / "scan_hbm_roofline.py")
    spec = importlib.util.spec_from_file_location("scan_hbm_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def rows(n64, n256):
        return {("ipt_bucket_rows_total", (("bucket", "64"),)): n64,
                ("ipt_bucket_rows_total", (("bucket", "256"),)): n256}

    ctx = {"trace": xplane.reduce_window(xplane.load(FIXTURE)),
           "window": scrape.Window(rows(50.0, 0.0), rows(10050.0, 1000.0)),
           "slice": scrape.Window(rows(9050.0, 900.0), rows(10050.0, 1000.0)),
           "config": {"scan_words": 225}, "device": {"kind": "TPU v5 lite"}}
    # bytes: 1000*(64+900) + 100*(256+900) + 6 launches * 256*225*4
    needed = 1000 * 964 + 100 * 1156 + 6 * 230400
    assert mod.read(ctx) == pytest.approx(
        100.0 * (needed / 819e9) / 561440e-9)
    assert 0.0 < mod.read(ctx) < 100.0
    ctx["device"] = {"kind": "TPU v9 imaginary"}
    with pytest.raises(KeyError):
        mod.read(ctx)
    ctx["trace"] = None
    assert mod.read(ctx) is None
    ctx.update(trace={"programs": {}}, slice=None)
    assert mod.read(ctx) is None
