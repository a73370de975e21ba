"""Model FLOPs of the prefills admitted and the tokens emitted in the
traced stretch, over the stretch x chips x bf16 peak."""
from chip_bench import work


def read(run):
    if run.trace is None or run.stretch is None:
        return None
    cfg, frames = run.cfg, run.frames
    prefills = sum(n for _, b, n in run.admits if run.in_stretch(b))
    flops = prefills * work.prefill_flops(cfg, frames)
    for r in run.requests.values():
        for k, tk in enumerate(r.token_t, start=1):
            if run.in_stretch(tk):
                flops += work.token_flops(cfg, k, frames)
    span = run.stretch[1] - run.stretch[0]
    chips = run.cell["chips"]
    return 100.0 * flops / (span * chips * run.peaks["bf16_flops_per_s"])
