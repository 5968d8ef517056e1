"""The reduction from a device trace to numbers, on a small recorded
trace: busy time as a union, idle gaps and what the host did in them,
and programs mapped to phases."""
import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import trace as T  # noqa: E402

DATA = pathlib.Path(__file__).with_name("data")


def small_trace() -> T.Trace:
    # ns; window 0..100. Ops overlap (10-30 and 20-40 merge to 10-40,
    # 50-70 and 60-80 to 50-80); the last runs past the window.
    ops = {"0": [(10, 30, "fusion.1"), (20, 40, "fusion.2"),
                 (50, 70, "all-reduce-start.3"), (60, 80, "fusion.4"),
                 (95, 120, "fusion.5")]}
    modules = {"0": [(10, 40, "jit_prefill(17)"),
                     (50, 80, "jit_decode_step(9)"),
                     (95, 120, "jit_decode_step(9)")]}
    spans = [(0, 100, "bench:window"), (40, 50, "bench:serve_tick"),
             (80, 95, "bench:wait_for_arrival")]
    return T.Trace(ops, modules, spans)


def test_busy_is_the_union_of_op_intervals_clipped_to_the_window():
    tr = small_trace()
    # [10,40] + [50,80] + [95,100] = 30 + 30 + 5
    assert T.busy_ns(tr, "0") == 65


def test_idle_gaps_cover_the_rest_of_the_window():
    tr = small_trace()
    assert T.idle_gaps(tr, "0") == [(0, 10), (40, 50), (80, 95)]
    assert T.length(T.idle_gaps(tr, "0")) + T.busy_ns(tr, "0") == 100


def test_gaps_are_named_by_the_host_span_open_at_their_middle():
    tr = small_trace()
    got = dict(T.gap_breakdown(tr, "0"))
    assert got == {"wait_for_arrival": 15, "outside-spans": 10,
                   "serve_tick": 10}


@pytest.mark.parametrize("name,phase", [
    ("jit_prefill(17)", "prefill"), ("jit_decode_step(9)", "decode"),
    ("jit_paged_insert_rows(3)", "prefill"), ("jit_argmax(2)", None)])
def test_programs_map_to_phases_by_the_table(name, phase):
    table = json.loads((DATA.parents[1] / "phases.json").read_text())
    assert T.phase_of(name, table["phases"]) == phase


def test_phase_time_and_counts_are_clipped_to_the_window():
    tr = small_trace()
    table = {"prefill": ["jit_prefill"], "decode": ["jit_decode_step"]}
    assert T.phase_ns(tr, "0", table) == {"prefill": 30, "decode": 35}
    assert T.module_count(tr, "0", table, "decode") == 2


def test_control_flow_that_encloses_ops_is_busy_but_not_work():
    tr = small_trace()
    tr.ops["0"].append((45, 90, "while.7"))
    assert "while" not in dict(T.top_ops(tr, "0"))
    assert T.busy_ns(tr, "0") == 80


def test_op_names_come_from_the_hlo_text():
    hlo = "%all-reduce-start.3 = f32[8]{0} all-reduce-start(%fusion.2)"
    assert T.op_name(hlo) == "all-reduce-start.3"
    assert T.op_kind(T.op_name(hlo)) == "all-reduce-start"
    assert T.op_name("%fusion.2 = f32[8] fusion(%all-reduce.1)") == \
        "fusion.2"


def test_summary_reports_busy_and_window_seconds():
    s = T.summary(small_trace(), 1)
    assert s["busy_s"] == pytest.approx(65e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    # ops are summed by kind (the name without its number)
    assert s["device_ops"][0] == ["fusion", pytest.approx(65e-9)]
    assert len(s["idle_gaps"]) == 3


def test_a_trace_survives_its_json_form():
    tr = small_trace()
    back = T.Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert T.busy_ns(back, "0") == T.busy_ns(tr, "0")
    assert back.window() == (0, 100)


def recorded():
    """20 ms of a traced hymba.train.1chip window on TPU v5e (times from
    the window's start, ns)."""
    return T.Trace.from_json(json.loads(
        (DATA / "hymba_train_tpu_20ms.json").read_text()))


def test_recorded_trace_busy_idle_and_gaps_add_up():
    tr = recorded()
    busy = T.busy_ns(tr, "0")
    idle = T.length(T.idle_gaps(tr, "0"))
    assert busy + idle == pytest.approx(20e6)
    assert 0.9 * 20e6 < busy < 20e6
    named = dict(T.gap_breakdown(tr, "0"))
    assert sum(named.values()) == pytest.approx(idle)
    assert "train_step" in named


def test_recorded_trace_programs_and_ops():
    tr = recorded()
    table = {"train": ["jit_sync_step"], "feed": ["jit_batch"]}
    ph = T.phase_ns(tr, "0", table)
    assert set(ph) == {"train", "feed"} and ph["train"] > 100 * ph["feed"]
    top = T.top_ops(tr, "0", 5)
    assert top[0][0] == "fusion" and all(k != "while" for k, _ in top)
    assert sum(v for _, v in T.top_ops(tr, "0", 1000)) <= \
        sum(e - s for s, e, n in tr.ops["0"] if not T.CONTAINER.match(n))
