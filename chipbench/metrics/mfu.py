"""Model FLOP utilisation, in percent: the FLOPs that LocalTrain needs
(``harness/counts.py``: forward of every layer and the head, the
backward that each round's freezing depth requires) in the rounds of the
window that ran after the profiler stopped, over their seconds on the
host's clock (from the end of the trace's write-out to the window's
close), over the chips' bf16 peak. The traced rounds are left out: the
profiler costs host time. Nothing to read where no round ran
untraced."""


def read(run):
    if run.peaks is None or run.untraced_rounds <= 0:
        return None
    chips = run.trace.n_devices if run.trace is not None else 1
    return 100.0 * run.untraced_flops / run.untraced_s / (
        max(chips, 1) * run.peaks["bf16_flops"])
