"""The whole serving step's share of the chip's bf16 peak, in %: model
FLOPs of every token processed in the window (``harness.arith.Dense``:
prefill with causal attention and last-position logits, each decode step
at its position) over the window's wall time, over the peak."""


def read(record):
    flops = record["counters"].get("model_flops")
    if not flops:
        return None
    return (100.0 * flops / record["window_s"]
            / record["peaks"]["bf16_flops_per_s"])
