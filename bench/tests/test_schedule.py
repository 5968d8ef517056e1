"""The open-loop schedule is a pure function of the traffic file, the
seed and the window: every seed gets the same lengths at the same
times, and its own token ids."""
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import common  # noqa: E402

serve = common.load_module(common.BENCH / "jobs" / "serve.py")
MIX = common.traffic_doc("chat.azure-conv")
BIG = 2 ** 31 + 12345


def key(reqs):
    return [(r.due, r.prompt_len, r.out_len, r.tokens.tobytes())
            for r in reqs]


def test_the_same_seed_gives_the_same_schedule():
    assert key(serve.schedule(MIX, BIG, 30)) == key(serve.schedule(MIX, BIG,
                                                                   30))


def test_seeds_share_sizes_and_times_and_differ_in_ids():
    a, b = serve.schedule(MIX, BIG, 30), serve.schedule(MIX, BIG + 1, 30)
    assert [(r.due, r.prompt_len, r.out_len) for r in a] == \
        [(r.due, r.prompt_len, r.out_len) for r in b]
    n = round(MIX["arrival"]["rate_per_s"] * 30)
    assert set(r.prompt_len for r in a) <= \
        set(serve.quantile_sizes(MIX["prompt"], n))
    assert all(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))


def test_the_order_of_sizes_comes_from_the_traffic_file():
    other = dict(MIX, arrival=dict(MIX["arrival"], order_seed=1))
    a, b = serve.schedule(MIX, BIG, 30), serve.schedule(other, BIG, 30)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert sorted(r.out_len for r in a)[:5] == \
        sorted(r.out_len for r in b)[:5]


def test_requests_fall_inside_the_window_at_the_offered_rate():
    reqs = serve.schedule(MIX, BIG, 30)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 30
    rate = MIX["arrival"]["rate_per_s"]
    assert abs(len(reqs) - rate * 30) <= 0.1 * rate * 30 + 2
    for r in reqs:
        assert MIX["prompt"]["min"] <= r.prompt_len <= MIX["prompt"]["max"]
        assert MIX["output"]["min"] <= r.out_len <= MIX["output"]["max"]
        assert len(r.tokens) == r.prompt_len
        assert np.all((0 <= r.tokens) & (r.tokens < MIX["vocab"]))


def test_quantile_sizes_keep_the_median_and_the_clip():
    sizes = serve.quantile_sizes({"median": 512, "sigma": 1.0, "min": 32,
                                  "max": 4096}, 101)
    assert sizes[50] == 512
    assert min(sizes) >= 32 and max(sizes) <= 4096


def test_buckets_cover_every_prompt_length_the_mix_can_send():
    bs = serve.buckets(MIX)
    for n in (MIX["prompt"]["min"], 100, 513, MIX["prompt"]["max"]):
        b = 8
        while b < n:
            b *= 2
        assert min(b, MIX["max_len"]) in bs

