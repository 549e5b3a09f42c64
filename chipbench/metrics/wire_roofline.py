"""The wire kernels' share of their HBM roofline, in percent.

Least time: the bytes the wire needs for every client of every traced
round shipped at q > 0 (``harness/counts.py``: each trainable element
read as float32, written as a code with its block's scale, read back,
written as float32), over the chips' HBM bandwidth. Time: the summed
device time of the wire kernels' programs in the trace (the jitted
Pallas quantize and dequantize calls, ``XLA Modules`` line of a TPU
trace). Nothing to read where no traced round shipped at q > 0; a
trace of such rounds that holds none of these programs is an error, not
a silent gap: the kernels were renamed or left the path, and the reader
has to follow them."""
from harness import counts

#: the wire kernels' jitted programs, as a TPU trace names them
KERNELS = ("jit_quantize_blocks", "jit_dequantize_blocks",
           "jit_quantize_topk_blocks")


def _is_wire(name):
    return name in KERNELS


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    need = 0.0
    masks = {}
    for cohort in run.cohorts:
        for (k, _s, _b, q, _ga) in cohort:
            if q == 0:
                continue
            if k not in masks:
                masks[k] = counts.trainable_elements(run.shapes, run.mask(k))
            need += counts.wire_bytes(masks[k], q)
    if need <= 0:
        return None
    spent = run.trace.seconds_of(_is_wire)
    if spent <= 0:
        found = sorted(run.trace.module_seconds,
                       key=lambda k: -run.trace.module_seconds[k])
        raise RuntimeError(f"wire_roofline: rounds shipped at q > 0 but the "
                           f"trace holds none of {KERNELS}; its programs: "
                           f"{found[:20]}")
    least = need / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / spent
