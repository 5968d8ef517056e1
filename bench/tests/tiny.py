"""Small shapes of the benchmark's configurations and mixes, for tests
on the CPU: the same families and code paths as the cells, at a size a
test run can hold."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import common  # noqa: E402

common.add_program_to_path()


def hymba():
    cfg = common.load_json(common.BENCH / "configs" / "hymba-1.5b-L8.json")
    cfg["model"].update(n_layers=2, d_model=160, n_heads=5, n_kv_heads=1,
                        d_ff=320, vocab_size=257, head_dim=32,
                        sliding_window=32, ssm_state=8, ssm_heads=5)
    mix = common.traffic_doc("train.s2048.b8.lr1e-5")
    mix.update(seq_len=128, trace_seconds=1)
    return cfg, mix


def minicpm3():
    cfg = common.load_json(common.BENCH / "configs" / "minicpm3-4b.json")
    cfg["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                        d_ff=128, vocab_size=257, head_dim=16,
                        q_lora_rank=32, kv_lora_rank=16, qk_rope_head_dim=8,
                        qk_nope_head_dim=16, v_head_dim=16)
    mix = common.traffic_doc("chat.azure-conv")
    mix.update(vocab=257, max_len=96, max_slots=4, kv_pages=0,
               trace_seconds=1.5,
               arrival={"process": "poisson", "rate_per_s": 4.0,
                        "order_seed": 7},
               prompt={"median": 16, "sigma": 1.0, "min": 8, "max": 64},
               output={"median": 8, "sigma": 0.8, "min": 2, "max": 32},
               sample={"min_tokens": 40, "max_requests": 4})
    return cfg, mix
