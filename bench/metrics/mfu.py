"""mfu.<cell>: percent of the chip's bf16 peak that the configuration's
own FLOP count of the requests finished in the window reaches
(FLOPs per item x items / window / peak)."""


def read(run):
    if run.peaks is None or not run.done:
        return None
    return 100.0 * run.flops_per_s() / run.peaks["bf16_flops_per_s"]
