"""Model FLOP/s utilization of the training step: the forward and backward
FLOPs a step requires (recompute not counted,
``counts.train_flops_per_step``) over the step program's device time (the
mean ``jit_step`` module time in the trace, on every chip), over chips
times the bf16 peak, in percent."""
from bench.lib.counts import train_flops_per_step

MODULES = ("jit_step",)


def read(run):
    t = run["traffic"]
    calls = run["trace"]["modules"].get("jit_step")
    if t["driver"] != "train" or not calls:
        return None
    step_s = sum(calls) / len(calls)
    flops = train_flops_per_step(run["config"], t["global_batch"], t["seq"])
    return 100.0 * flops / step_s / (run["chips"]
                                     * run["peak"]["bf16_flops_per_s"])
