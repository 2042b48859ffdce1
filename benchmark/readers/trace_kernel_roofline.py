"""A set of kernels' share of their roofline: the least time the chip
could take for every call in the trace, from shapes alone
(benchmark/flops.py, benchmark/peaks.json), over the kernels' summed
device time.

kfx's Pallas kernels carry no name in the trace (``kernel_metadata={}``;
PERF.md, for the tracing issue): a flash kernel is a ``custom-call``
whose target is ``tpu_custom_call``, told apart by its signature, which
the metric's file gives: how many operands it takes and whether it
returns a tuple (forward: q, k, v -> (o, lse); dq: six operands -> one
tensor; dkv: six operands -> (dk, dv)).

args: {"target": "tpu_custom_call",
       "kernels": {"fwd": {"operands": 3, "tuple": true}, ...}}.
Shapes are the cell's: batch rows a chip (global batch / data ways),
the configuration's heads a chip and head size, the mix's sequence."""

from benchmark import flops, peaks


def signature(name):
    """(operands, returns a tuple) of an op's long HLO name."""
    head, _, rest = name.partition(" = ")
    call = rest.split(" custom-call(", 1)
    if len(call) != 2:
        return None
    return call[1].split("), custom_call_target", 1)[0].count(" %"), \
        rest.startswith("(")


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
        if f'custom_call_target="{args["target"]}"' not in name:
            continue
        sig = signature(name)
        for kind, want in args["kernels"].items():
            if sig == (want["operands"], want["tuple"]):
                f, b = flops.flash_kernel_cost(
                    kind, batch, heads, mix["sequence_tokens"],
                    flops.head_dim(cfg))
                least += flops.least_seconds(f, b, peak)[0] \
                    * tr["op_counts"][name]
                spent += seconds
    return 100.0 * least / spent if spent > 0 else None
