"""Share of the device's busy time spent in sort ops: the batch closure's
dedupe and canonical orders (`core/merge.py`, `u64.lexsort`)."""


def read(ctx):
    s = ctx.summary
    if s is None or s.busy_s <= 0:
        return None
    sort_s = sum(v for k, v in s.op_s.items() if "sort" in k.lower())
    if sort_s <= 0:
        return None
    return 100.0 * sort_s / s.busy_s
