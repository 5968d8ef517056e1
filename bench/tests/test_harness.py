"""The harness's own plumbing: which per-layer metrics a cell reports, and
the record of where a window stalled."""
import gc
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import common  # noqa: E402

run = common.load_module(common.BENCH / "run.py")


def test_a_cell_reports_the_per_layer_metrics_that_list_it():
    bench = common.benchmark()
    names = {c["name"] for c in bench["workloads"]}
    for cell in names:
        got = {m["name"] for m in run.per_layer_metrics(bench, cell)}
        assert got == {m["name"] for m in bench["per_layer"]
                       if cell in m["workloads"]}
        assert got, f"{cell} reports no per-layer metric"
        # each moves an end-to-end metric that the cell reports
        e2e = set(run.cell_e2e(bench, cell))
        assert {m["moves"] for m in run.per_layer_metrics(bench, cell)} <= e2e
    for m in bench["per_layer"]:
        assert set(m["workloads"]) <= names


def test_stalls_keep_the_longest_marked_gaps(capsys):
    s = common.Stalls()
    for pause in (0.0, 0.03, 0.0):
        s.begin()
        time.sleep(pause)
        s.mark()
    s.report("tick", n=1)
    err = capsys.readouterr().err
    assert len(s.gaps) == 3
    assert max(s.gaps)[0] >= 0.03
    assert "longest ticks: 0.03" in err and "(of 3)" in err


def test_stalls_record_garbage_collections_until_reported():
    s = common.Stalls()
    gc.collect()
    assert s.gcs and s.gcs[-1][2] == 2
    s.report("step")
    n = len(s.gcs)
    gc.collect()
    assert len(s.gcs) == n
