"""Roofline share of an op: model bytes over peak bandwidth x busy time."""

from hkvbench import spec


def share(ctx, op: str):
    s = ctx.summary
    if s is None or s.busy_s <= 0 or ctx.window.keys <= 0:
        return None
    need = spec.byte_model(op).bytes_per_key(ctx.run.cell.config)
    least_s = ctx.window.keys * need / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / s.busy_s
