"""Model FLOP/s utilisation of the whole serving step, in percent: the
algorithmic operations of every prefill and decode step in the traced
window (from the shapes) over window x chips x the bf16 peak."""


def read(run):
    if run.trace is None:
        return None
    from chipbench.devcalls import decode_work, prefill_work
    flops = sum(w["flops"] for w in decode_work(run) + prefill_work(run))
    if not flops:
        return None
    return 100.0 * flops / (run.trace.window_s * run.chips
                            * run.peaks["flops_per_s"])
