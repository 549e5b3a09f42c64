"""Wire-kernel launches per traced round, quantize and dequantize each
counted (the program's ``wire_calls`` counter, ``kernels/ops.py``)."""
from harness import program_spans


def read(run):
    return program_spans.count_per_round(run, "wire_calls")
