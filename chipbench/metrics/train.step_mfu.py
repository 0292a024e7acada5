"""Step's share of the chip's bf16 peak: the FLOPs the window's steps
require (``chipbench.flops``, PaLM's 6N + 12LHQT, recompute not counted)
over the sum of the harness's dispatch-to-``block_until_ready`` step
spans, over the device's peak."""


def read(rec):
    steps = rec.spans.get("step")
    if not steps:
        return None
    flops = rec.window.info["flops_per_step"] * len(steps)
    return 100.0 * flops / sum(steps) / rec.peaks["bf16_flops"]
