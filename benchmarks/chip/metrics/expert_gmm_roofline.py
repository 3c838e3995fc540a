"""Roofline share of the expert layer's grouped matmul: the least time for
a layer's expert work over the device time it took (%), one ``(FLOPs,
bytes)`` for every layer.

A step of T tokens (decode rows plus chunk tokens) routes T·k rows, of
which r = T·k·held/E fall on the held experts, and touches
hit = held·(1 − (1 − k/E)^T) of them. FLOPs are 2·r·3·d·f; bytes are
hit·3·d·f·2 B of expert weights plus r·(2·d + 3·f)·2 B of rows in and
out. r and hit are expectations under uniform routing, the assumption
``counts.Model`` makes for expert FLOPs; the program's ``moe_rows`` ÷
``moe_assign`` counters on ``serve.step`` check r. A model without expert
layers reads 0.

The time is that of the three megablox ``gmm`` calls a layer (gate, in,
out) and of the fusions that stage their weights: the layer scan slices
each layer's held-expert matrices, bf16 (held, d, f) and (held, f, d), out
of the stacked leaves into a buffer of their own (in VMEM where it fits)
before the kernel runs, so the HBM read of the weights happens there and
not inside ``gmm``.

Matched by signature, as the trace does not name Pallas kernels: ``gmm`` is
a 2-d output from five scalar-prefetched operands (the tile count, the
group offsets, group ids and m-tile ids, the group offset s32[1]), then the
rows (2-d bf16) and the experts' matrices (3-d bf16); HLO printed with
operand shapes may number the sixth operand (``/*index=5*/``). A staging
fusion is a ``fusion`` whose output is one such matrix stack.
"""

from chipbench import reduce

PATTERN = (r"= (?:f32|bf16)\[\d+,\d+\]\S* custom-call\(s32\[\]\S* %[^,\s]+, "
           r"s32\[\d+\]\S* %[^,\s]+, s32\[\d+\]\S* %[^,\s]+, "
           r"s32\[\d+\]\S* %[^,\s]+, s32\[1\]\S* %[^,\s]+, "
           r"(?:/\*index=5\*/)?bf16\[\d+,\d+\]\S* %[^,\s]+, "
           r"bf16\[\d+,\d+,\d+\]\S* "
           r"%[^,\s]+\).*tpu_custom_call")


def work(model, tokens: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's grouped matmuls for a step of this
    many tokens, expected under uniform routing."""
    e = model.moe
    if e is None or tokens == 0:
        return 0, 0
    d, f, k = model.d, e.d_ff, e.per_token
    rows = tokens * k * e.held / e.experts
    hit = e.held * (1 - (1 - k / e.experts) ** tokens)
    return (2 * rows * 3 * d * f,
            hit * 3 * d * f * 2 + rows * (2 * d + 3 * f) * 2)


def pattern(model) -> str:
    """The grouped matmul's kernel calls and its weight-staging fusions."""
    e = model.moe
    stack = rf"{e.held},(?:{model.d},{e.d_ff}|{e.d_ff},{model.d})"
    return rf"{PATTERN}|= bf16\[{stack}\]\S* fusion\("


def read(run):
    if run.model.moe is None:
        return 0.0          # no expert layer: no grouped-matmul work to share
    return reduce.roofline(run, pattern(run.model), lambda s: work(
        run.model, len(s.decode_ctxs) + (s.chunk[1] if s.chunk else 0)))
