"""Named kernels' share of their roofline: the least time the chip
could take for every call in the trace, from shapes alone
(benchmark/flops.py, benchmark/peaks.json), over the kernels' summed
device time. A kernel is found by the name the program gave it
(``pl.pallas_call(..., name=...)``), which a TPU trace shows as the
custom call's own instruction name
(``%kfx_flash_dq.11 = ... custom-call(...)``); no operand is counted.
A program whose kernels carry no such name gives nothing to read.

args: {"kernels": {"fwd": "kfx_flash_fwd", ...}}: the cost function's
kind (benchmark/flops.py flash_kernel_cost) -> the kernel's name.
Shapes are the cell's: batch rows a chip (global batch / data ways),
the configuration's heads a chip and head size, the mix's sequence."""

from benchmark import flops, peaks


def named(op_text, kernel):
    """Whether an op's long HLO text is the custom call named
    ``kernel`` (with the compiler's ``.<n>`` behind the name), and not
    an op that only consumes its result."""
    head, _, rest = op_text.partition(" = ")
    return " custom-call(" in rest and \
        head.strip().lstrip("%").rsplit(".", 1)[0] == kernel


def read(ctx, args):
    tr = ctx.get("trace")
    if not tr or "worker" not in ctx:
        return None
    cfg, mix, plan = ctx["cfg"], ctx["mix"], ctx["worker"]["plan"]
    peak = peaks.peaks(ctx["device"]["kind"])
    batch = mix["global_batch_sequences"] // plan["dp"]
    heads = cfg["num_attention_heads"] // plan["tp"]
    least = spent = 0.0
    for name, seconds in tr["op_seconds"].items():
        for kind, kernel in args["kernels"].items():
            if named(name, kernel):
                f, b = flops.flash_kernel_cost(
                    kind, batch, heads, mix["sequence_tokens"],
                    flops.head_dim(cfg))
                least += flops.least_seconds(f, b, peak)[0] \
                    * tr["op_counts"][name]
                spent += seconds
    return 100.0 * least / spent if spent > 0 else None
