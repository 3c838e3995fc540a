"""Roofline share of the paged decode kernel: the least time for its
work (max of FLOPs over peak and bytes over bandwidth, per call, from
counts.Model.decode_kernel) over its summed time in the trace (%).
Decode is bound by memory: G = H / K FLOPs per byte of KV.

The trace does not name Pallas kernels (the event is the HLO text of a
``tpu_custom_call``), so the kernel is matched by its signature: a 3-d
bf16 output (B*K, G, hd) from scalar-prefetched block tables (2-d),
context lengths (1-d) and a block mask (2-d), then the q and page pools.
"""

from chipbench import reduce

PATTERN = (r"= bf16\[\d+,\d+,\d+\]\S* custom-call\(s32\[\d+,\d+\]\S* "
           r"%[^,\s]+, s32\[\d+\]\S* %[^,\s]+, s32\[\d+,\d+\]\S* "
           r"%[^,\s]+, bf16.*tpu_custom_call")


def read(run):
    return reduce.roofline(run, PATTERN,
                           lambda s: run.model.decode_kernel(s.decode_ctxs))
