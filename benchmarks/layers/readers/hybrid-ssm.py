"""Reader `hybrid-ssm`: a hybrid state-space serving step from the
device trace, the device seconds by named scope (lib/scope_time.py) and
the program's `sequence` counters, which the device program itself
counted (live scan chunks, causal pairs). A scope's share of the busy
time is reader `latent-moe`'s `scope_share`, as it stands.

args: {"stat": "mfu", "steps": regex} - the model FLOPs of the traced
steps' REAL work (lib/hybrid_ssm_counts.py: matmuls by real tokens, the
scan by its live chunks, attention by causal pairs, from what
/stats.json counted in the window, a step being one operation matching
`steps`) over the chip's peak FLOP/s times the device's busy time, in
percent: the share of the whole step;
{"stat": "scan_roofline" | "attention_roofline", "scopes": regex,
"steps": regex} - the least time the chip could take for what the live
chunks / the causal pairs need (the larger of operations over peak
FLOP/s and bytes over peak bytes/s) over the device seconds of the
scopes, in percent. None where the program keeps no such counters, the
capture has no scope map, or the trace holds no step."""

from lib import hybrid_ssm_counts as counts, peaks, scope_time
from lib.evidence import trace_ops

NEEDED = ("steps", "rows", "tokensReal", "pairsCausal", "ssmChunks")


def gained(evidence, key):
    before = (evidence.get("stats_before") or {}).get("sequence")
    after = (evidence.get("stats_after") or {}).get("sequence")
    if not before or not after or key not in before or key not in after:
        return None
    return after[key] - before[key]


def read(args, evidence):
    trace, scoped = evidence.get("trace"), evidence.get("scopes")
    if not trace or trace["busy_s"] <= 0:
        return None
    steps, _seconds = trace_ops(evidence, args["steps"])
    counted = {k: gained(evidence, k) for k in NEEDED}
    model = evidence["shapes"].get("model")
    if (steps == 0 or model is None or "mamba_d_state" not in model
            or any(v is None for v in counted.values())
            or counted["steps"] <= 0):
        return None
    # what a step of the window held on average, times the traced steps
    per = {k: v / counted["steps"] * steps for k, v in counted.items()}
    kind = evidence["device_kind"]
    if args["stat"] == "mfu":
        need = counts.step_counts(
            model, per["tokensReal"], per["pairsCausal"], per["ssmChunks"],
            per["rows"], evidence["shapes"]["n_items"])
        peak = peaks.peaks_for(kind)["flops_per_s"]
        return 100.0 * need["flops"] / (peak * trace["busy_s"])
    if not scoped:
        return None
    seconds = scope_time.seconds_of(scoped, args["scopes"])
    if seconds <= 0:
        return None
    if args["stat"] == "scan_roofline":
        need = counts.scan_counts(model, per["ssmChunks"])
    elif args["stat"] == "attention_roofline":
        need = counts.attention_counts(model, per["pairsCausal"],
                                       per["tokensReal"])
    else:
        raise ValueError(f"hybrid-ssm: unknown stat {args['stat']!r}")
    least, _bound = peaks.roofline_seconds(need["flops"], need["bytes"], kind)
    return 100.0 * least / seconds
