"""Whole step: operations the SOI schedule requires for the window's work
(prompts prefilled and tokens decoded in it), over the window's seconds
times the chip's peak bf16 FLOP/s (%)."""


def read(run):
    f = run.model_flops("all")
    return 100.0 * f / (run.seconds * run.peak["bf16_flops_s"]) if f else None
