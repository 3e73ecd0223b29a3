"""Required training FLOPs a token (lib/peaks.train_flops_per_token: 6 per
matmul parameter plus causal attention, recomputation not counted) times
the tokens a second of the untraced part of the window, over chips times
the chip's bf16 peak."""
from perfbench.lib import peaks


def read(ctx):
    r = ctx["result"]
    if not ctx["peaks"]:
        return None
    rate = r["untraced_tokens"] / r["untraced_s"]
    need = peaks.train_flops_per_token(ctx["config"], ctx["traffic"]["seq"])
    return 100.0 * need * rate / (r["chips"] * ctx["peaks"]["bf16_tflops"] * 1e12)
