"""Model FLOP utilisation of the whole train step, in % of the chips' bf16
peak: model FLOPs per token (the cell's family's ``train_flops_per_token``)
× tokens per second of the traced window ÷ (chips × peak)."""


def read(f):
    if f.steps == 0:
        return None
    per_token = f.family.train_flops_per_token(f.dims,
                                               f.traffic["seq_len"])
    tokens_per_s = f.tokens_per_step * f.steps / f.window_s
    return 100 * per_token * tokens_per_s / (f.chips * f.peaks["bf16_flops_per_s"])
