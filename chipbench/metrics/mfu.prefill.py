"""Model FLOP/s utilisation of prefill alone, in percent: the algorithmic
operations of the prefills in the traced window over window x chips x the
bf16 peak. It bounds what prefill_roofline can claim end to end."""


def read(run):
    if run.trace is None:
        return None
    from chipbench.devcalls import prefill_work
    flops = sum(w["flops"] for w in prefill_work(run))
    if not flops:
        return None
    return 100.0 * flops / (run.trace.window_s * run.chips
                            * run.peaks["flops_per_s"])
