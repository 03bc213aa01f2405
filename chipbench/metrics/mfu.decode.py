"""Model: counted model FLOPs of every token processed in the traced window
(the busy lanes of each decode step, and the prompts prefilled) over the
window's seconds times the chip's peak, in percent."""


def read(run):
    if run.trace is None:
        return None
    mf = run.cost("model_forward")
    flops = sum(mf.decode(run.config, c["contexts"])
                for c in run.calls_in_window("decode"))
    flops += sum(mf.prefill(run.config, c["tokens"])
                 for c in run.calls_in_window("prefill"))
    if not flops:
        return None
    return 100.0 * flops / (run.trace.window_s
                            * run.peaks["bf16_flops_per_s"])
