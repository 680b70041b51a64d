"""Real queries over padded batch slots, summed over the window's
compiled batches (the executor's ``batch`` spans: ``n`` and ``B``)."""


def read(run):
    n = slots = 0
    for name, _t0, _dur, _depth, args in run.spans:
        if name == "batch" and "B" in args:
            n += int(args["n"])
            slots += int(args["B"])
    if slots == 0:
        return None
    return 100.0 * n / slots
