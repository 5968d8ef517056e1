"""Model FLOP utilization of the traced training window: the
configuration's forward+backward FLOPs per token (its own function, no
recomputation) times the tokens the window trained, over the window's
length times the chips times the chip's bf16 peak. Source: device trace
(the window is the traced bench:window span)."""


def read(ctx):
    tr = ctx["trace"]
    if not ctx.get("tokens_traced"):
        return None
    lo, hi = tr.window()
    seconds = (hi - lo) / 1e9
    flops = ctx["reference"].train_flops_per_token(
        ctx["cfg"], ctx["traffic"]["seq_len"]) * ctx["tokens_traced"]
    return 100.0 * flops / (seconds * ctx["chips"]
                            * ctx["peaks"]["bf16_flops"])
