"""Model step: the model's least operations for the tokens prefilled and
decoded in the window (``model_ops`` of the configuration's reference
module), over the window's seconds and the chip's bf16 peak, in percent."""


def read(ctx):
    if ctx.peak is None:
        return None
    w = ctx.window
    prompts, contexts = [], []
    for r in ctx.timeline:
        for j, t in enumerate(r.stamps):
            if not w.t0 < t <= w.t1:
                continue
            if j == 0:
                prompts.append(r.prompt_len)
            else:
                contexts.append(r.prompt_len + j)
    ops = ctx.ref.model_ops(ctx.arch, prompt_lens=prompts, decode_contexts=contexts)
    if not ops:
        return None
    return ops / ((w.t1 - w.t0) * ctx.peak["bf16_flops"]) * 100.0
