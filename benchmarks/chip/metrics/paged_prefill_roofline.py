"""Roofline share of the paged chunked-prefill kernel: the least time
for the chunk's causal attention over its visible context (per call, from
counts.Model.chunk_kernel) over its summed time in the trace (%).

Matched by signature, as the trace does not name Pallas kernels: a 4-d
bf16 output (B*K, C, G, hd) from scalar-prefetched block tables (2-d),
context lengths, query lengths (1-d each) and a block mask (2-d).
"""

from chipbench import reduce

PATTERN = (r"= bf16\[\d+,\d+,\d+,\d+\]\S* custom-call\(s32\[\d+,\d+\]\S* "
           r"%[^,\s]+, s32\[\d+\]\S* %[^,\s]+, s32\[\d+\]\S* %[^,\s]+, "
           r"s32\[\d+,\d+\]\S* %[^,\s]+, bf16.*tpu_custom_call")


def read(run):
    return reduce.roofline(
        run, PATTERN,
        lambda s: run.model.chunk_kernel(*s.chunk) if s.chunk else (0, 0))
