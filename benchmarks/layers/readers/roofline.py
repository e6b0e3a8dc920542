"""Reader `roofline`: the least time the chip could take for the work
(the larger of operations over peak FLOP/s and bytes over peak bytes/s,
both counted from shapes by benchmarks/lib/counts.py) over the device
time the trace shows, in percent.

args: {"count": "topk", "pattern": regex of the kernel's operations} -
per call of the kernel, the batch being the mean real rows per dispatch
that /stats.json counted in the window; or {"count": "als_iteration"} -
the device's busy time in the traced window over its iterations."""

from lib import counts, peaks
from lib.evidence import stats_delta, trace_ops


def read(args, evidence):
    trace = evidence.get("trace")
    if not trace:
        return None
    kind, shapes = evidence["device_kind"], evidence["shapes"]
    if args["count"] == "topk":
        calls, seconds = trace_ops(evidence, args["pattern"])
        rows = stats_delta(evidence, ["batching", "batchedQueries"],
                           ["batching", "batches"])
        if calls == 0 or rows is None:
            return None
        need = counts.topk_counts(rows, shapes["n_items"], shapes["dim"],
                                  shapes["k"])
        least, _bound = peaks.roofline_seconds(need["flops"], need["bytes"],
                                               kind)
        return 100.0 * least * calls / seconds
    if args["count"] == "als_iteration":
        iters = shapes["iterations_in_window"]
        if trace["busy_s"] <= 0 or iters <= 0:
            return None
        need = counts.als_iteration_counts(
            shapes["n_ratings"], shapes["n_users"], shapes["n_items"],
            shapes["rank"], shapes["cg_iters"])
        least, _bound = peaks.roofline_seconds(need["flops"], need["bytes"],
                                               kind)
        return 100.0 * least * iters / trace["busy_s"]
    raise ValueError(f"roofline: unknown count {args['count']!r}")
