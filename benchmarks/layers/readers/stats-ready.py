"""Reader `stats-ready`: what the server's own start-up record held when
the window began, absolute and not a difference: `startup.phases` of the
/stats.json taken before the window, a list of [name, seconds,
hostBytesInUse] in the order the phases ended (a child phase before its
parent, so phases are picked by their whole name, never by prefix).

args: {"stat": "sum_seconds", "names": [phase, ...]} - the seconds of the
phases so named, added up; or {"stat": "max_host_bytes"} - the largest
memory in use of the host at the end of any phase. None where the server
keeps no such record (a program from before it) or no phase matches."""


def read(args, evidence):
    stats = evidence.get("stats_before") or {}
    phases = (stats.get("startup") or {}).get("phases") or []
    if args["stat"] == "sum_seconds":
        names = set(args["names"])
        picked = [seconds for name, seconds, _bytes in phases if name in names]
        return sum(picked) if picked else None
    if args["stat"] == "max_host_bytes":
        held = [b for _name, _seconds, b in phases if b is not None]
        return max(held) if held else None
    raise ValueError(f"stats-ready: unknown stat {args['stat']!r}")
