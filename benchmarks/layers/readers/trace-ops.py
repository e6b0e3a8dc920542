"""Reader `trace-ops`: device time of the operations whose name matches,
from the profiler's trace. args: {"pattern": regex, "stat":
"ms_per_call" | "seconds"}."""

from lib.evidence import trace_ops


def read(args, evidence):
    calls, seconds = trace_ops(evidence, args["pattern"])
    if calls == 0:
        return None
    return seconds * 1e3 / calls if args["stat"] == "ms_per_call" else seconds
