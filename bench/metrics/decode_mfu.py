"""decode_mfu: model operations of the decode tokens served in the traced
window (the reference module's ``decode_token_ops`` at each token's
context) over the window's length times the chip's int8 peak, in %.
Every product on the path is int8 x int8, hence the int8 peak."""

#: the reference module's count this reads; ``spec.cell`` checks it is there
NEEDS = ("decode_token_ops",)


def read(run):
    count = getattr(run.ref, "decode_token_ops", None)
    steps = run.steps
    if count is None or run.trace is None or not steps or run.peaks is None:
        return None
    ops = sum(count(run.arch, n) for s in steps for n in s.lengths)
    return 100.0 * ops / (run.trace["window_ns"] / 1e9 * run.peaks["int8_ops_per_s"])
