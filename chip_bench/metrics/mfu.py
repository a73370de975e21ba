"""Model FLOPs of the prefills admitted and the tokens emitted in the
traced stretch, over the stretch x chips x bf16 peak. The FLOPs are the
model family's (``families/<name>.py``), counted per request from its
input size."""


def read(run):
    if run.trace is None or run.stretch is None:
        return None
    fam, cfg = run.family, run.cfg
    flops = 0
    for r in run.requests.values():
        if r.admitted is not None and run.in_stretch(r.admitted):
            flops += fam.prefill_flops(cfg, r.size)
        for k, tk in enumerate(r.token_t, start=1):
            if run.in_stretch(tk):
                flops += fam.token_flops(cfg, k, r.size)
    span = run.stretch[1] - run.stretch[0]
    chips = run.cell["chips"]
    return 100.0 * flops / (span * chips * run.peaks["bf16_flops_per_s"])
