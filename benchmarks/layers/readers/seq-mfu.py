"""Reader `seq-mfu`: a sequence-serving step from the device trace and
the program's `sequence` counters.

args: {"stat": "step_ms", "loop": regex} - the device's busy time in the
traced window over its steps (one loop, the operations matching `loop`,
a step); {"stat": "loop_share", "loop": regex} - the device time of those
loops over that busy time, in percent (the loop is ONE `while` a step
with no `while` inside it, so no time is counted twice); or
{"stat": "mfu", "loop": regex} - the model FLOPs of a step's
REAL tokens (lib/seq_counts.py, from what /stats.json counted in the
window: tokens, attention pairs, rows, steps) times the traced steps,
over the chip's peak FLOP/s times that busy time, in percent: the share
of the whole step, padding and every kernel's extra passes being time
and not work. None where the program keeps no such counters or the
trace holds no loop."""

from lib import peaks, seq_counts
from lib.evidence import trace_ops


def gained(evidence, key):
    before = (evidence.get("stats_before") or {}).get("sequence")
    after = (evidence.get("stats_after") or {}).get("sequence")
    if not before or not after or key not in before or key not in after:
        return None
    return after[key] - before[key]


def read(args, evidence):
    trace = evidence.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    steps, loop_seconds = trace_ops(evidence, args["loop"])
    if steps == 0:
        return None
    if args["stat"] == "step_ms":
        return trace["busy_s"] * 1e3 / steps
    if args["stat"] == "loop_share":
        return 100.0 * loop_seconds / trace["busy_s"]
    if args["stat"] != "mfu":
        raise ValueError(f"seq-mfu: unknown stat {args['stat']!r}")
    counted = {k: gained(evidence, k)
               for k in ("steps", "tokensReal", "attentionPairs", "rows")}
    shapes = evidence["shapes"]
    if (any(v is None for v in counted.values()) or counted["steps"] <= 0
            or "model" not in shapes):
        return None
    n = counted["steps"]
    need = seq_counts.looped_lm_counts(
        shapes["model"], counted["tokensReal"] / n,
        counted["attentionPairs"] / n, counted["rows"] / n,
        shapes["n_items"])
    peak = peaks.peaks_for(evidence["device_kind"])["flops_per_s"]
    return 100.0 * need["flops"] * steps / (peak * trace["busy_s"])
