"""Reader `stats-delta`: what /stats.json counted inside the window.

args: {"terms": [{"num": path, "den": path}, ...], "scale": x}. Each term
is (num after - num before) / (den after - den before); the metric is the
terms' sum times `scale`. (The program's histograms have doubling
buckets, so a median read from them would be good to a factor of two
only; a `sum` over a `count` is the window's exact mean.)"""

from lib.evidence import stats_delta


def read(args, evidence):
    parts = [stats_delta(evidence, t["num"], t["den"]) for t in args["terms"]]
    if any(p is None for p in parts):
        return None
    return sum(parts) * args.get("scale", 1.0)
