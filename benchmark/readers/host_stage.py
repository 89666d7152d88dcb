"""A stage of the Optimizer's own ``Metrics`` (host clock around each
part of an iteration), read once per step by the harness's summary hook
and differenced to per-step seconds."""

from benchmark import stats


def read(ctx, stage: str, stat: str):
    per_step = ctx["stage_per_step"].get(stage, [])[:ctx["steps"]]
    if not per_step:
        return None
    if stat == "share_pct":
        return 100.0 * sum(per_step) / ctx["window_s"]
    if stat == "p90_ms":
        return 1e3 * stats.percentile(per_step, 90)
    if stat == "mean_ms":
        return 1e3 * sum(per_step) / len(per_step)
    raise ValueError(f"unknown stat {stat!r}")
