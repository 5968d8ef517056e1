"""Device time by the program's own names: layer scopes read from each
op's op_name, the program's "repro:" host spans, and the metrics that
read them, on synthetic traces and on a hand-encoded trace file."""
import json
import os
import pathlib
import struct
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import common, scopes as S, trace as T  # noqa: E402

DATA = pathlib.Path(__file__).with_name("data")
NEW_METRICS = ("attention_share.train", "mamba_share.train",
               "mlp_share.train", "accumulate_share.train",
               "optimizer_share.train", "decode_attention_share.serve",
               "tick_host_ms.serve")


def scoped_trace():
    # ns; window 0..100. A prefill program (0-40), then two decode
    # programs (50-92, 95-120); each op's name-stack path beside it.
    ops = {"0": [(0, 10, "fusion.1"), (10, 20, "fusion.2"),
                 (20, 40, "fusion.3"), (50, 60, "fusion.4"),
                 (60, 75, "fusion.5"), (75, 90, "while.6"),
                 (80, 90, "fusion.7"), (95, 110, "fusion.8")]}
    paths = {"0": ["jit(prefill)/while/body/attention/dot_general",
                   "jit(prefill)/while/body/mlp/dot_general",
                   "jit(prefill)/attention_out/add",
                   "jit(decode_step)/while/body/attention/reduce_max",
                   "jit(decode_step)/while/body/mlp/dot_general",
                   "jit(decode_step)/while/body/attention/while",
                   "jit(decode_step)/while/body/attention/exp;add",
                   "jit(decode_step)/transpose(jvp())/attention/mul"]}
    modules = {"0": [(0, 40, "jit_prefill(3)"),
                     (50, 92, "jit_decode_step(9)"),
                     (95, 120, "jit_decode_step(9)")]}
    spans = [(0, 100, "bench:window"),
             (0, 46, "bench:serve_tick"), (47, 100, "bench:serve_tick")]
    program = [(1, 45, "repro:serve.step"), (38, 45, "repro:serve.commit"),
               (48, 100, "repro:serve.step")]
    return T.Trace(ops, modules, spans), S.Names(paths, program)


def test_scope_time_is_the_union_of_its_ops_clipped_to_the_window():
    tr, nm = scoped_trace()
    # fusion.1, .4, .7 (10 each) and .8 clipped to 5; the while that
    # encloses fusion.7 is not work; "attention_out" is another segment
    assert S.scope_ns(tr, nm, "0", "attention") == 35
    assert S.scope_ns(tr, nm, "0", "mlp") == 25


def test_scope_time_inside_the_given_programs_only():
    tr, nm = scoped_trace()
    assert S.scope_ns(tr, nm, "0", "attention", ["jit_decode_step"]) == 25
    assert S.scope_ns(tr, nm, "0", "mlp", ["jit_prefill"]) == 10
    assert S.scope_ns(tr, nm, "0", "mlp", ["jit_argmax"]) == 0


def test_a_scope_no_op_carries_reads_as_absent():
    tr, nm = scoped_trace()
    assert S.scope_ns(tr, nm, "0", "mamba") is None
    assert S.scope_share(tr, nm, "0", "mamba") is None
    # busy: [0,40] + [50,90] + [95,100]
    assert S.scope_share(tr, nm, "0", "mlp") == pytest.approx(100 * 25 / 85)


def metric(name):
    return common.load_module(common.BENCH / "metrics" / f"{name}.py")


def test_decode_attention_share_is_over_the_decode_programs_time():
    tr, nm = scoped_trace()
    ctx = {"trace": tr, "names": nm,
           "phases": {"prefill": ["jit_prefill"],
                      "decode": ["jit_decode_step"]}}
    # decode programs clipped to the window: 42 + 5
    got = metric("decode_attention_share.serve").read(ctx)
    assert got == pytest.approx(100 * 25 / 47)


def test_tick_host_time_is_device_idle_inside_the_program_ticks():
    tr, nm = scoped_trace()
    # idle gaps (40,50) and (90,95): 5 inside the first tick, 2 + 5
    # inside the second
    assert S.span_idle_ns(tr, nm, "0", "serve.step") == (12, 2)
    got = metric("tick_host_ms.serve").read({"trace": tr, "names": nm})
    assert got == pytest.approx(6 / 1e6)


def test_host_activity_prefers_the_innermost_program_span():
    tr, nm = scoped_trace()
    assert S.host_activity(tr, nm, 42) == "serve.commit"
    assert S.host_activity(tr, nm, 30) == "serve.step"
    assert S.host_activity(tr, nm, 45.5) == "serve_tick"
    assert dict(S.gap_breakdown(tr, nm, "0")) == {"serve_tick": 10,
                                                  "serve.step": 5}
    # the benchmark's own reduction is left as it was
    assert dict(T.gap_breakdown(tr, "0")) == {"serve_tick": 15}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_trace_without_program_names_reads_no_new_metric(name):
    tr = T.Trace.from_json(json.loads(
        (DATA / "hymba_train_tpu_20ms.json").read_text()))
    ctx = {"trace": tr, "names": S.Names({}, []),
           "phases": {"decode": ["jit_decode_step"]}}
    assert metric(name).read(ctx) is None


# ----------------------------------------------- a hand-encoded XSpace
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def msg(*fields) -> bytes:
    """A protobuf message: ints as varints, str/bytes length-delimited,
    floats as fixed 64 bits."""
    out = b""
    for num, value in fields:
        if isinstance(value, float):
            out += _varint(num << 3 | 1) + struct.pack("<d", value)
        elif isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def entry(key: int, value: bytes) -> bytes:
    return msg((1, key), (2, value))


def xspace(program_spans: bool = True) -> bytes:
    """A TPU plane whose op metadata carry their op_name as the stat
    "tf_op" (by reference to a stat metadata, and as a string), beside
    other stats; and a host plane with the benchmark's and the
    program's spans."""
    stat_meta = [(1, "tf_op"), (2, "jit(f)/while/body/attention/dot"),
                 (3, "flops"), (4, "hlo_category")]
    op_meta = [
        (10, "%fusion.1 = f32[8]{0} fusion(), kind=kLoop",
         [msg((1, 1), (7, 2)), msg((1, 3), (3, 100))]),
        (11, "%copy.2 = f32[8]{0} copy(f32[8]{0} %p)",
         [msg((1, 4), (5, "data formatting")),
          msg((1, 1), (5, "jit(f)/mlp/exp"))]),
        (12, "%while.3 = (f32[8]) while((f32[8]) %t)", [])]
    tpu = msg((1, 7), (2, "/device:TPU:0"),
              (3, msg((1, 1), (2, "XLA Modules"), (3, 1000),
                      (4, msg((1, 20), (2, 0), (3, 30_000))))),
              (3, msg((1, 2), (2, "XLA Ops"), (3, 1000),
                      *[(4, msg((1, m), (2, off), (3, 10_000)))
                        for m, off in [(10, 0), (11, 10_000), (12, 20_000),
                                       (10, 40_000)]])),
              *[(4, entry(i, msg((1, i), (2, n), *[(5, s) for s in st])))
                for i, n, st in op_meta],
              (4, entry(20, msg((1, 20), (2, "jit_f(42)")))),
              *[(5, entry(i, msg((1, i), (2, n)))) for i, n in stat_meta])
    names = [(1, "bench:window"), (2, "repro:serve.step"), (3, "other")]
    host = msg((1, 8), (2, "/host:CPU"),
               (3, msg((1, 1), (2, "python3"), (3, 1000),
                       (4, msg((1, 1), (2, 0), (3, 100_000))),
                       *([(4, msg((1, 2), (2, 5_000), (3, 30_000)))]
                         if program_spans else []),
                       (4, msg((1, 3), (2, 6_000), (3, 1_000))))),
               *[(4, entry(i, msg((1, i), (2, n)))) for i, n in names])
    return msg((1, tpu), (1, host))


def written(tmp_path, buf: bytes) -> str:
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(buf)
    return str(path)


def test_op_names_come_from_the_op_metadata_stats():
    buf = xspace()
    [(dev, plane)] = list(S.device_planes(buf))
    events = list(S.op_events(buf, plane))
    assert dev == "0"
    assert [T.op_name(n) for n, _ in events] == [
        "fusion.1", "copy.2", "while.3", "fusion.1"]
    assert [st.get("tf_op") for _, st in events] == [
        "jit(f)/while/body/attention/dot", "jit(f)/mlp/exp", None,
        "jit(f)/while/body/attention/dot"]
    assert events[0][1]["flops"] == 100


def test_paths_align_with_the_benchmark_s_own_record(tmp_path):
    path = written(tmp_path, xspace())
    tr = T.load(path)
    nm = S.load(path, tr)
    assert [op[2] for op in tr.ops["0"]] == [
        "fusion.1", "copy.2", "while.3", "fusion.1"]
    assert nm.paths == {"0": ["jit(f)/while/body/attention/dot",
                              "jit(f)/mlp/exp", "",
                              "jit(f)/while/body/attention/dot"]}
    assert [s[2] for s in nm.spans] == ["repro:serve.step"]
    assert [s[2] for s in tr.spans] == ["bench:window"]
    # ops at 1000 + 0..10 and 1000 + 40..50 ns
    assert S.scope_ns(tr, nm, "0", "attention") == 20
    assert S.scope_ns(tr, nm, "0", "attention", ["jit_f"]) == 10
    # idle 1030..1040 and 1050..1100; the span is 1005..1035
    assert S.span_idle_ns(tr, nm, "0", "serve.step") == (5, 1)


def test_a_trace_the_record_does_not_match_gives_no_paths(tmp_path):
    path = written(tmp_path, xspace())
    tr = T.load(path)
    tr.ops["0"] = tr.ops["0"][:-1]
    assert S.load(path, tr).paths == {}


def test_metrics_find_the_newest_trace_file(tmp_path, monkeypatch):
    old = tmp_path / "a" / "old.xplane.pb"
    old.parent.mkdir()
    old.write_bytes(xspace(program_spans=False))
    new = written(tmp_path, xspace())
    os.utime(old, (1, 1))
    monkeypatch.setattr(S, "TRACE_DIR", tmp_path)
    ctx = {"trace": T.load(new)}
    assert [s[2] for s in S.names(ctx).spans] == ["repro:serve.step"]
    assert metric("tick_host_ms.serve").read(ctx) == pytest.approx(5e-6)
