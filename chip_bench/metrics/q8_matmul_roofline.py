"""Share of its roofline the q8_matmul kernel reaches in the traced
stretch: the least time the chip could take for the kernel's calls (the
larger of operations over peak and bytes over bandwidth, from each call's
HLO shapes), over the time they took."""
from chip_bench import work

KERNEL = "q8_matmul"


def read(run):
    t = run.trace
    if t is None or not t["kernels"].get(KERNEL):
        return None
    least = spent = 0.0
    for hlo, secs in t["kernels"][KERNEL]:
        ops, nbytes = work.kernel_call(hlo)
        least += max(ops / run.peaks["bf16_flops_per_s"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
        spent += secs
    return 100.0 * least / spent if spent > 0 else None
