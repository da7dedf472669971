"""Model FLOP/s utilization of the whole step: the operations that the
prompt tokens prefilled and the output tokens generated in the window
require (routed top-k and shared experts only), over window x peak, in %.

Prompt tokens served from the prefix cache are not processed and do not
count.  An output token counts one token's layers at its context plus
the output head."""

import flops


def read(ctx):
    conf = ctx.conf
    ops = 0.0
    for _, n, hit in ctx.admissions:
        ops += flops.prompt_ops(conf, hit, n - hit)
    for _, lens in ctx.decodes:
        for keys in lens:
            ops += flops.token_ops(conf, keys, True)
    if not ops:
        return None
    return 100.0 * ops / (ctx.seconds * ctx.peak()["flops"])
