"""Training model FLOP/s utilisation in the traced steps: six FLOPs per
active weight and three times the forward attention per token
(``counts.train_flops_per_token``; recomputation not counted), times the
tokens the traced steps completed, over the traced window times the
chip's peak."""


def read(ctx):
    if ctx["kind"] != "train" or "flops" not in ctx:
        return None
    return 100.0 * ctx["flops"] / (ctx["window_s"]
                                   * ctx["peak"]["bf16_flops_per_s"])
