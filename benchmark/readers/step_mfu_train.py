"""The whole step's share of the chips' peak: operations the forward and
backward passes need for the real tokens trained (no recomputation, no
padding), over time x chips x peak. In a traced run the profiler's start
stalls the host for seconds, and the loop waits on every step, so the
share is taken over the steps that ended before the profiler started;
where the steps' end times are not known one by one, over the whole
window."""

from benchmark import flops


def read(facts):
    work = facts["work"]
    if not work.get("steps"):
        return None
    docs, seconds = work["doc_lengths"], facts["window_s"]
    traced = facts.get("trace_window")
    times = work.get("step_times") or []
    if traced and traced[0] and len(times) == len(work["step_docs"]):
        before = [i for i, t in enumerate(times) if t <= traced[0]]
        if before:
            docs = [n for i in before for n in work["step_docs"][i]]
            seconds = times[before[-1]] - facts["t0"]
    need = flops.train_flops(facts["dims"], docs,
                             trainable=work["trainable"],
                             lora_rank=work["lora_rank"])
    return 100.0 * need / (seconds * facts["chips"]
                           * facts["peaks"]["flops_bf16"])
