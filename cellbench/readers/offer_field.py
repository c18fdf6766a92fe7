"""One field of the hand-over accounting (`harness.Offer`), e.g. `gen_s_max`:
the longest the generator held a drain up before handing over."""


def read(ctx, args):
    return float(getattr(ctx.offer, args["field"]))
