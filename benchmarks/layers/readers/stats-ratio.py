"""Reader `stats-ratio`: what one counter of /stats.json gained inside
the window over what another gained, times `scale`; None where the
program keeps either counter not (reader `stats-delta` raises there: it
reads what every program of the benchmark's first day had).

args: {"num": path, "den": path, "scale": x}, a path being the keys from
the top of /stats.json."""


def dig(stats, path):
    for key in path:
        if not isinstance(stats, dict) or key not in stats:
            return None
        stats = stats[key]
    return stats


def read(args, evidence):
    before, after = evidence.get("stats_before"), evidence.get("stats_after")
    ends = [dig(s, args[k]) for s in (before, after) for k in ("num", "den")]
    if any(v is None for v in ends):
        return None
    num0, den0, num1, den1 = ends
    if den1 - den0 <= 0:
        return None
    return (num1 - num0) / (den1 - den0) * args.get("scale", 1.0)
