"""The whole step's share of the chips' peak for the hybrid state-space /
attention routed decoder: ``step_mfu_mla_train`` with ``flops_ssm``'s
arithmetic (a state-space layer's two projections and its scan where
such a layer stands, attention in the attention layers alone, experts
billed by the program's count of held pairs). Over the steps that ended
before the profiler started in a traced run, else over the whole window.
The share cannot pass 100: it counts no operation twice and none that
the step does not need."""

from benchmark import flops_ssm


def read(facts):
    work = facts["work"]
    if not work.get("steps") or "step_pairs" not in work \
            or "ssm_heads" not in facts.get("dims", {}):
        return None
    steps = list(range(len(work["step_docs"])))
    seconds = facts["window_s"]
    traced = facts.get("trace_window")
    times = work.get("step_times") or []
    if traced and traced[0] and len(times) == len(steps):
        before = [i for i, t in enumerate(times) if t <= traced[0]]
        if before:
            steps, seconds = before, times[before[-1]] - facts["t0"]
    if len(work["step_pairs"]) != len(work["step_docs"]):
        return None
    need = flops_ssm.train_flops(
        facts["dims"], work["layer_kinds"],
        [n for i in steps for n in work["step_docs"][i]],
        held_pairs=sum(work["step_pairs"][i] for i in steps),
        lora_rank=work["lora_rank"], lora_targets=work["lora_targets"])
    return 100.0 * need / (seconds * facts["chips"]
                           * facts["peaks"]["flops_bf16"])
