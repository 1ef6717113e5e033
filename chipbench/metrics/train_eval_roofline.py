"""The fused train + eval program's share of its roofline: the rounds'
required FLOPs (each client's submodel: 3x forward per trained sample,
1x per eval sample) over the chips' bf16 peak times the program's device
time on chip 0. With the cohort sharded, every chip trains its own
share of the clients in that time, so the peak is the chips' together.
Bounded by compute alone (its bytes are not counted), so the share reads
low, never high."""


def compute(run):
    tr = run.trace
    if tr is None:
        return None
    ns = tr.module_ns("_client_train_eval")
    flops = run.counters.get("required_flops", 0.0)
    if ns <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (run.device["count"] * run.peaks["flops_bf16"]
                            * ns / 1e9)
