"""train_mfu: useful local-training FLOPs in the window over the window's
seconds times the chip's bf16 peak.  Useful FLOPs are 3 x forward FLOPs x
local iterations x batch x real participants per training dispatch; the
padding rows of the participant bank do not count."""


def read(ctx):
    if not ctx["useful_flops"]:
        return None
    return 100.0 * ctx["useful_flops"] / (ctx["wall_s"]
                                          * ctx["peak"]["bf16_flops_per_s"])
